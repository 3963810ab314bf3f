"""The ``sdar_moe`` forward pass (JetLM SDAR-30B-A3B) in plain float32
``jax.numpy``, and its generation by diffusion over blocks as a plain
Python loop over that forward: the reference the served path is held to.

No kernels, no cache, no batching, a loop over experts; every matrix
product at ``highest`` precision.  The layer is Qwen3-MoE's
(``config.json`` names the sizes; the equations are the family's modeling
code, from memory: the configuration file's ``assumed``):

- every layer: ``a = h + Wo Attn(N1(h))``; ``h' = a + MoE(N2(a))``, RMS
  norms ``x * rsqrt(mean(x^2) + eps) * w``;
- attention: ``q = x Wq`` (``num_attention_heads`` of ``head_dim``), ``k = x
  Wk``, ``v = x Wv`` (``num_key_value_heads``: query head h reads K/V head
  ``h // group``), no biases; RMS norm over ``head_dim`` on each head of q
  and of k; rotary (``rope_theta``, no scaling, pairs ``(i, i + head_dim /
  2)``) on q and k at the row's own position; scores ``q . k /
  sqrt(head_dim)``; **row i attends row j iff ``j // B <= i // B``** (``B``
  = ``block_length``: causal across blocks, full inside one);
- MoE: ``p = softmax(x Wr)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest; weights ``p_e / sum of the chosen p``;
  expert e ``W2_e (silu(W1_e x) * W3_e x)`` of ``moe_intermediate_size``;
- final RMS norm, untied head; logit row i predicts position i ITSELF.

Generation (``generate``; the family's ``generate.py``): the prompt's
``(P // B) * B`` leading tokens are context; then block by block, the
block's ``B`` positions hold the prompt's last ``P mod B`` tokens (first
block only) and the mask token elsewhere.  A DENOISE pass forwards context +
block and, at each masked position, takes ``x0 = argmax`` with confidence
``softmax(logits)[x0]``, and unmasks by the rule (``unmask_rule``: a plain
NumPy statement of ``low_confidence_static`` / ``low_confidence_dynamic``);
once no mask is left the block is committed (appended to the context).

Departures from the published code: none in the mathematics of the layer
or of the static rule.  ``generate.py`` keeps a ``DynamicCache`` and slices
a dense mask; this file keeps NOTHING between passes (every pass is a full
forward over the stream's tokens) and builds the mask from positions.  A
pass's static count is stated from what is STILL masked (``pass_count``:
an even split over the passes left) — under the static rule the same
numbers as the block's even split with the remainder first
(``static_counts``); under the dynamic rule, where an earlier pass may have
unmasked more than its share, the published code keeps the block's first
split, and this count is then the smaller.  The DEPTH is the configuration
file's; no dropout (evaluation).

It reads the parameter tree ``models.sdar.sdar_init`` produces (weights
``[in, out]``, routed experts ``[E, F, H]``, one dict a layer) and upcasts
each tensor where it is used: attention in query blocks, the experts one at
a time, the head in slices of the vocabulary.  ``sizes`` is the
configuration file's dict (published keys + ``assumed``).

``cast`` rounds every matrix product's operands to a narrower type first:
what computing in that precision would give (a control).  ``block_length``
overrides the configuration's (a control: the mask of another model).
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HEAD_SLICES = 16


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, cos, sin):
    """Pairs (i, i + D/2) of the last axis rotated by frequency i."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def block_length_of(sizes: dict) -> int:
    return int((sizes.get("assumed") or {}).get(
        "block_length", sizes.get("block_length", 4)))


def mask_token_of(sizes: dict) -> int:
    return int((sizes.get("assumed") or {}).get(
        "mask_token_id", sizes.get("mask_token_id", 151669)))


def route(x, router, sizes: dict):
    """x [S, H] fp32 -> (ids [S, k], weights [S, k], margin [S]): the
    published statement — a softmax over ALL experts, the k largest,
    divided by their sum; the margin is how far (in logits) the routing is
    from another outcome: the k-th largest less the (k+1)-th."""
    k = int(sizes["num_experts_per_tok"])
    logits = x @ router.astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    top, ids = lax.top_k(p, k + 1)
    w = top[:, :k]
    if sizes.get("norm_topk_prob", True):
        w = w / w.sum(-1, keepdims=True)
    ranked = jnp.take_along_axis(logits, ids, axis=1)
    return ids[:, :k], w, ranked[:, k - 1] - ranked[:, k]


def forward(params, tokens, sizes: dict, *, out_positions, q_block: int = 128,
            block_length=None, cast=None):
    """tokens int32 [S] (the mask token's id where a position is masked) ->
    (logits float32 [len(out_positions), V], routing margin
    [len(out_positions)]: the least over the layers at that position)."""
    with jax.default_matmul_precision("highest"):
        f32 = (lambda a: a.astype(jnp.float32)) if cast is None else \
            (lambda a: a.astype(cast).astype(jnp.float32))

        def mm(a, b):
            return f32(a) @ f32(b)
        eps = float(sizes["rms_norm_eps"])
        H = int(sizes["hidden_size"])
        nH, nKV, D = (int(sizes["num_attention_heads"]),
                      int(sizes["num_key_value_heads"]),
                      int(sizes["head_dim"]))
        grp = nH // nKV
        E = int(sizes["num_experts"])
        B = int(block_length or block_length_of(sizes))
        S = tokens.shape[0]
        inv = float(sizes["rope_theta"]) ** (
            -np.arange(0, D, 2, dtype=np.float64) / D)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
            * jnp.asarray(inv, jnp.float32)[None]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        nb = -(-S // q_block)
        pad = nb * q_block - S

        def attention(p, x):
            h = _rms(x, p["input_norm"], eps)
            q = _rms(mm(h, p["wq"]).reshape(S, nH, D), p["q_norm"], eps)
            k = _rms(mm(h, p["wk"]).reshape(S, nKV, D), p["k_norm"], eps)
            v = mm(h, p["wv"]).reshape(S, nKV, D)
            q, k = _rope(q, cos, sin), _rope(k, cos, sin)
            qf = jnp.pad(f32(q), ((0, pad), (0, 0), (0, 0))) \
                .reshape(nb, q_block, nKV, grp, D)
            kf, vf = f32(k), f32(v)
            cols = jnp.arange(S)[None, :]

            def block(i):
                rows = (i * q_block + jnp.arange(q_block))[:, None]
                ok = cols // B <= rows // B
                s = jnp.einsum("qnmd,tnd->nmqt", qf[i], kf) * D ** -0.5
                s = jnp.where(ok[None, None], s, -jnp.inf)
                return jnp.einsum("nmqt,tnd->qnmd", f32(jax.nn.softmax(s, -1)),
                                  vf)
            a = lax.map(block, jnp.arange(nb)).reshape(nb * q_block,
                                                       nH * D)[:S]
            return x + mm(a, p["wo"])

        def experts(p, h):
            ids, w, margin = route(h, p["router"], sizes)

            def expert(e, y):
                we = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
                # [F, H] as held: gate/up contract H, down maps F -> H
                g = f32(h) @ f32(p["w_gate"][e]).T
                u = f32(h) @ f32(p["w_up"][e]).T
                return y + we[:, None] * (f32(jax.nn.silu(g) * u)
                                          @ f32(p["w_down"][e]))
            return lax.fori_loop(0, E, expert, jnp.zeros_like(h)), margin

        x = params["embed"][tokens].astype(jnp.float32)
        margins = []
        for p in params["layers"][:int(sizes["num_hidden_layers"])]:
            x = attention(p, x)
            y, margin = experts(p, _rms(x, p["post_attn_norm"], eps))
            margins.append(margin)
            x = x + y
        out = jnp.asarray(out_positions, jnp.int32)
        h = _rms(x[out], params["final_norm"], eps)
        head = params["lm_head"]
        n = HEAD_SLICES if head.shape[0] % HEAD_SLICES == 0 else 1
        logits = lax.map(lambda rows: mm(h, rows.T),
                         head.reshape(n, head.shape[0] // n, H))
        logits = jnp.moveaxis(logits, 0, 1).reshape(len(out), head.shape[0])
        return logits, jnp.min(jnp.stack(margins), axis=0)[out]


# --------------------------------------------------------------------- #
# Generation: the rules in plain NumPy, the loop in plain Python
# --------------------------------------------------------------------- #
def static_counts(masked: int, denoising_steps: int) -> list:
    """Positions each of a block's ``denoising_steps`` passes unmasks under
    the static schedule, for a block that starts with ``masked`` positions
    masked: an even split, the remainder to the first passes."""
    base, rem = divmod(int(masked), int(denoising_steps))
    return [base + (i < rem) for i in range(int(denoising_steps))]


def pass_count(masked_now: int, passes_done: int, denoising_steps: int
               ) -> int:
    """The static count of a pass from what is still masked: an even split
    over the passes left, rounded up."""
    return -(-int(masked_now) // max(int(denoising_steps) - int(passes_done),
                                     1))


def unmask_rule(masked, conf, count: int, rule: str, threshold: float):
    """One block's pass: ``masked`` [B] bool, ``conf`` [B] the proposals'
    confidences, ``count`` the static schedule's number for this pass ->
    the positions unmasked [B] bool.  ``low_confidence_static``: the
    ``count`` masked positions of highest confidence;
    ``low_confidence_dynamic``: every masked position over ``threshold``,
    and if those are fewer than ``count``, the ``count`` of highest
    confidence.  (A tie goes to the earlier position.)"""
    masked = np.asarray(masked, bool)
    c = np.where(masked, np.asarray(conf, np.float64), -np.inf)
    order = np.argsort(-c, kind="stable")
    top = np.zeros(len(c), bool)
    top[order[:min(int(count), int(masked.sum()))]] = True
    if rule == "low_confidence_static":
        return top & masked
    if rule != "low_confidence_dynamic":
        raise ValueError(rule)
    high = masked & (c > threshold)
    return high if high.sum() >= count else top & masked


def confidences(logits):
    """(``x0`` = argmax, ``softmax(logits)[x0]``) a row, float32 in, the
    probability in float64."""
    lg = np.asarray(logits, np.float64)
    x0 = lg.argmax(-1)
    top = lg.max(-1, keepdims=True)
    return x0, 1.0 / np.exp(lg - top).sum(-1)


def generate(logits_of, prompt, gen_length: int, *, block_length: int,
             mask_token_id: int, denoising_steps: int, rule: str,
             threshold: float = 0.9, trace=None):
    """``gen_length`` tokens after ``prompt`` by diffusion over blocks.
    ``logits_of(tokens [n], positions)`` is the forward pass (no cache:
    the whole row every time).  ``trace``: a list that receives, a pass,
    (block start, the block's input ids with -1 where masked, logits [B, V]
    or None for a commit pass, positions unmasked)."""
    B = int(block_length)
    prompt = [int(t) for t in prompt]
    done = prompt[:len(prompt) // B * B]
    block = prompt[len(done):] + [-1] * (B - len(prompt) + len(done))
    total = len(prompt) + int(gen_length)
    while len(done) < total:
        start = len(done)
        for step in range(denoising_steps + 1):
            masked = np.asarray([t < 0 for t in block])
            row = done + [mask_token_id if t < 0 else t for t in block]
            if not masked.any():
                # the commit pass: its K/V rows are the block's (a path
                # with a cache forwards the block once more; this one
                # keeps nothing, so there is nothing to compute)
                if trace is not None:
                    trace.append((start, list(block), None, None))
                break
            logits = np.asarray(logits_of(
                np.asarray(row, np.int32), list(range(start, start + B))))
            x0, conf = confidences(logits)
            take = unmask_rule(
                masked, conf, pass_count(masked.sum(), step, denoising_steps),
                rule, threshold)
            if trace is not None:
                trace.append((start, list(block), logits, take))
            block = [int(x0[i]) if take[i] else t
                     for i, t in enumerate(block)]
        assert all(t >= 0 for t in block), "a block ends with no mask left"
        done += block
        block = [-1] * B
    return done[len(prompt):total]
