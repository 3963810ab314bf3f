"""Operations and bytes the algorithm needs, computed from shapes alone.

``sizes`` is a configuration file's dict (the published GPT-2 keys plus
``assumed.vocab_rows_held``).  Recomputed operations (remat) never count.
"""


def _dims(sizes: dict):
    H, L = int(sizes["n_embd"]), int(sizes["n_layer"])
    F = int(sizes.get("n_inner") or 4 * H)
    V = int(sizes.get("assumed", {}).get("vocab_rows_held",
                                         sizes["vocab_size"]))
    return H, L, F, V


def matmul_params(sizes: dict) -> int:
    """Weights that take part in a matrix multiplication: per layer
    qkv (H x 3H), attention projection (H x H), the two FFN matrices
    (2 x H x F); plus the tied unembedding (V x H), which is a real
    GEMM forward and backward.  Embedding and position lookups are
    gathers and count nothing."""
    H, L, F, V = _dims(sizes)
    return L * (3 * H * H + H * H + 2 * H * F) + V * H


def num_params(sizes: dict) -> int:
    """Every trained element (weights, biases, LayerNorm, embeddings)."""
    H, L, F, V = _dims(sizes)
    S = int(sizes["n_positions"])
    per_layer = (2 * H) + (3 * H * H + 3 * H) + (H * H + H) + (2 * H) \
        + (H * F + F) + (F * H + H)
    return V * H + S * H + L * per_layer + 2 * H


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    """6 x matmul parameters (forward 2, backward 4) + the attention
    score and value products, 12 x layers x width x sequence (PaLM
    appendix B counting: full S x S products, causal skipping not
    credited)."""
    H, L, _, _ = _dims(sizes)
    return 6.0 * matmul_params(sizes) + 12.0 * L * H * seq_len


def adam_step_bytes(n_elements: int, param_itemsize: int,
                    moment_itemsize: int) -> int:
    """Least HBM traffic of one Adam update over ``n_elements``: params,
    grads and both moments read once, params and both moments written
    once.  Grads are counted at the parameter's own width (a bf16
    backward yields bf16 grads): a kernel that reads wider grads moves
    more than it must, and its share of the roofline says so."""
    return n_elements * (3 * param_itemsize + 4 * moment_itemsize)
