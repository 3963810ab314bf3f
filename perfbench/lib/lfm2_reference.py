"""The ``lfm2_moe`` forward pass (Liquid LFM2-24B-A2B) in plain float32
``jax.numpy``: the reference the served logits and conv pages are held to.

No kernels, no cache, no batching, a loop over experts; every matrix
product at ``highest`` precision.  It follows the ``lfm2_moe`` modeling
code of ``transformers`` (``config.json`` names the sizes, not these
equations).  With ``u = RMSNorm(h; g, norm_eps)`` = ``h * rsqrt(mean(h^2) +
eps) * g``, for layer ``l``:

    h <- h + Op_l(RMSNorm(h; g_op));   h <- h + FFN_l(RMSNorm(h; g_ffn))

- ``conv`` (gated short convolution, ``conv_L_cache`` = L taps, no bias):
  ``[B | C | X] = u W_in`` (in that order); ``z = B * X``; ``c_t = sum_{j
  < L} k[:, j] * z_{t-(L-1)+j}`` with ``z_{<0} = 0`` (depthwise, causal, per
  channel; ``k[:, L-1]`` meets the current token); ``y = (C * c) W_out``.
  No position enters.  A stream's state at position t is ``(z_{t-L+2}, ..,
  z_t)``;
- ``full_attention``: ``q = u Wq`` (``num_attention_heads`` of ``hidden_size
  / num_attention_heads``), ``k = u Wk``, ``v = u Wv``
  (``num_key_value_heads``: query head h reads K/V head ``h // group``), no
  biases; RMS norm over the head on q and on k; rotary (``rope_theta``, no
  scaling, pairs ``(i, i + D/2)``) on q and k of EVERY attention layer;
  causal softmax(``q . k / sqrt(D)``) over keys ``j <= i``; ``o = A Wo``;
- layers ``< num_dense_layers``: ``down(silu(gate u) * up u)`` of
  ``intermediate_size``;
- every other layer: ``s = sigmoid(u Wr)``; the ``num_experts_per_tok``
  largest ``s + b`` are chosen (``use_expert_bias``: b for choosing only);
  weights ``s`` at the chosen / (their sum + 1e-6) (``norm_topk_prob``) x
  ``routed_scaling_factor``; each expert a gated SiLU FFN of
  ``moe_intermediate_size``; NO shared expert;
- one RMS norm (the family's ``embedding_norm``), then the head, tied to
  the embedding.

Departures from the published model: none in a layer (every expert, every
head and the whole vocabulary are here); the DEPTH is the configuration
file's: ``layer_types`` (kept whole in the file) gives its first
``num_dense_layers`` entries and then the entries from the PUBLISHED
``num_dense_layers`` on, ``num_hidden_layers`` in all (``layer_types``
below); no dropout (evaluation).

It reads the parameter tree ``models.lfm2.lfm2_init`` produces (weights
``[in, out]``, routed experts ``[E, F, H]``, the filter ``[H, L]``, one dict
a layer) and upcasts each tensor where it is used: attention runs in query
blocks, the dense FFN in row blocks, the experts one at a time and the head
in slices of the vocabulary, so that 13k positions fit beside the engine.
``sizes`` is the configuration file's dict (published keys).

Switches used ONLY for the controls that show the comparison can fail:
``zero_state_at`` = P (a traced scalar; 0 changes nothing) makes every conv
layer at rows ``t >= P`` read zeros for the rows before P: what a stream
that resumed at P WITHOUT its snapshot would compute.  ``cast`` rounds every
matrix product's operands to a narrower type first: what computing in that
precision would give.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HEAD_SLICES = 16
ROW_BLOCK = 1024


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, cos, sin):
    """Pairs (i, i + D/2) of the last axis rotated by frequency i."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer_types(sizes: dict) -> list:
    """The kinds of the layers held: see the header."""
    n, dense = int(sizes["num_hidden_layers"]), int(sizes["num_dense_layers"])
    skip = int((sizes.get("published") or {}).get("num_dense_layers", dense))
    types = list(sizes["layer_types"])
    return types[:dense] + types[skip:skip + n - dense]


def route(x, router, bias, sizes: dict):
    """x [S, H] fp32 -> (ids [S, k], weights [S, k], margin [S]): the
    margin is how far (in ``c = s + b``) the routing is from another
    outcome: the k-th largest ``c`` less the (k+1)-th."""
    k = int(sizes["num_experts_per_tok"])
    s = jax.nn.sigmoid(x @ router.astype(jnp.float32))
    top, ids = lax.top_k(s + bias.astype(jnp.float32), k + 1)
    w = jnp.take_along_axis(s, ids[:, :k], axis=1)
    if sizes.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return ids[:, :k], w * float(sizes["routed_scaling_factor"]), \
        top[:, k - 1] - top[:, k]


def forward(params, tokens, sizes: dict, *, out_positions, q_block: int = 128,
            cast=None, zero_state_at=0, state_at=None):
    """tokens int32 [S] -> (logits float32 [len(out_positions), V], routing
    margin [len(out_positions)]: the least over the expert layers at that
    position) and, where ``state_at`` = t is given, every conv layer's state
    at position t, ``[conv layers, conv_L_cache - 1, H]``: rows ``z_{t-L+2}
    .. z_t``."""
    with jax.default_matmul_precision("highest"):
        f32 = (lambda a: a.astype(jnp.float32)) if cast is None else \
            (lambda a: a.astype(cast).astype(jnp.float32))

        def mm(a, b):
            return f32(a) @ f32(b)
        eps = float(sizes["norm_eps"])
        H = int(sizes["hidden_size"])
        nH, nKV = (int(sizes["num_attention_heads"]),
                   int(sizes["num_key_value_heads"]))
        D, grp = H // nH, nH // nKV
        E, L = int(sizes["num_experts"]), int(sizes["conv_L_cache"])
        S = tokens.shape[0]
        theta = float(sizes["rope_parameters"]["rope_theta"])
        inv = theta ** (-np.arange(0, D, 2, dtype=np.float64) / D)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
            * jnp.asarray(inv, jnp.float32)[None]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        nb = -(-S // q_block)
        pad = nb * q_block - S
        rows = jnp.arange(S)
        cut = jnp.asarray(zero_state_at, jnp.int32)

        def conv(p, x):
            u = _rms(x, p["op_norm"], eps)
            b, c, xx = jnp.split(mm(u, p["w_in"]), 3, axis=-1)
            z = b * xx                                           # [S, H]
            zp = jnp.pad(z, ((L - 1, 0), (0, 0)))    # z_t at row t + L - 1
            k = p["conv_k"].astype(jnp.float32)                  # [H, L]
            mixed = jnp.zeros_like(z)
            for j in range(L):
                src = rows - (L - 1) + j         # the position tap j reads
                lost = (rows >= cut) & (src < cut)     # (the control only)
                mixed = mixed + jnp.where(lost[:, None], 0.0,
                                          zp[j:j + S] * k[:, j])
            return x + mm(c * mixed, p["w_out"]), zp

        def attention(p, x):
            u = _rms(x, p["op_norm"], eps)
            q = _rms(mm(u, p["wq"]).reshape(S, nH, D), p["q_norm"], eps)
            k = _rms(mm(u, p["wk"]).reshape(S, nKV, D), p["k_norm"], eps)
            v = mm(u, p["wv"]).reshape(S, nKV, D)
            q, k = _rope(q, cos, sin), _rope(k, cos, sin)
            qf = jnp.pad(f32(q), ((0, pad), (0, 0), (0, 0))) \
                .reshape(nb, q_block, nKV, grp, D)
            kf, vf = f32(k), f32(v)
            cols = jnp.arange(S)[None, :]

            def block(i):
                at = (i * q_block + jnp.arange(q_block))[:, None]
                s = jnp.einsum("qnmd,tnd->nmqt", qf[i], kf) * D ** -0.5
                s = jnp.where((cols <= at)[None, None], s, -jnp.inf)
                return jnp.einsum("nmqt,tnd->qnmd",
                                  f32(jax.nn.softmax(s, -1)), vf)
            a = lax.map(block, jnp.arange(nb)).reshape(nb * q_block,
                                                       nH * D)[:S]
            return x + mm(a, p["wo"])

        def ffn(x, gate_w, up, down):
            return mm(jax.nn.silu(mm(x, gate_w)) * mm(x, up), down)

        def dense(p, u):
            n = -(-S // ROW_BLOCK)
            ub = jnp.pad(u, ((0, n * ROW_BLOCK - S), (0, 0))) \
                .reshape(n, ROW_BLOCK, H)
            y = lax.map(lambda r: ffn(r, p["mlp_gate"], p["mlp_up"],
                                      p["mlp_down"]), ub)
            return y.reshape(n * ROW_BLOCK, H)[:S]

        def experts(p, u):
            ids, w, margin = route(u, p["router"], p["router_bias"], sizes)

            def expert(e, y):
                we = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
                # [F, H] as held: gate/up contract H, down maps F -> H
                g = f32(u) @ f32(p["w_gate"][e]).T
                up = f32(u) @ f32(p["w_up"][e]).T
                return y + we[:, None] * (f32(jax.nn.silu(g) * up)
                                          @ f32(p["w_down"][e]))
            return lax.fori_loop(0, E, expert, jnp.zeros_like(u)), margin

        x = params["embed"][tokens].astype(jnp.float32)
        margins, states = [], []
        for l, (p, kind) in enumerate(zip(params["layers"],
                                          layer_types(sizes))):
            if kind == "conv":
                x, zp = conv(p, x)
                if state_at is not None:
                    # rows z_{t-L+2} .. z_t = zp rows t + 1 .. t + L - 1
                    states.append(lax.dynamic_slice(
                        zp, (jnp.asarray(state_at, jnp.int32) + 1, 0),
                        (L - 1, H)))
            else:
                x = attention(p, x)
            u = _rms(x, p["ffn_norm"], eps)
            if l < int(sizes["num_dense_layers"]):
                x = x + dense(p, u)
            else:
                y, margin = experts(p, u)
                margins.append(margin)
                x = x + y
        out = jnp.asarray(out_positions, jnp.int32)
        h = _rms(x[out], params["final_norm"], eps)
        head = params["embed"]
        n = HEAD_SLICES if head.shape[0] % HEAD_SLICES == 0 else 1
        logits = lax.map(lambda r: mm(h, r.T),
                         head.reshape(n, head.shape[0] // n, H))
        logits = jnp.moveaxis(logits, 0, 1).reshape(len(out), head.shape[0])
        margin = jnp.min(jnp.stack(margins), axis=0)[out] if margins \
            else jnp.full((len(out),), jnp.inf)
        if state_at is None:
            return logits, margin
        return logits, margin, jnp.stack(states)
