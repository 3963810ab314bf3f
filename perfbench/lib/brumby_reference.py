"""The ``brumby`` forward pass (Brumby-14B-Base: every layer a
power-retention layer) in plain float32 ``jax.numpy``: the reference the
served logits are held to.

No kernels, no state, no chunks, no cache, no batching: the QUADRATIC form
of the retention over the whole context, every matrix product at
``highest`` precision.  A layer (all norms ``x * rsqrt(mean(x^2) + eps) *
w``)::

    h += Ret(norm1(h));   h += Wdown(silu(Wgate x) * Wup x),  x = norm2(h)

    q = x Wq [nH x D]   k = x Wk [nKV x D]   v = x Wv [nKV x D]
    q, k: per-head RMS norm (weight [D]), then rotary positions on all D
          dimensions, pairs (i, i + D/2), theta = rope_theta, no scaling
    log g = log_sigmoid(x Wg + bg) [nKV];   G_i = sum_{l <= i} log g_l
    A_ij  = exp(G_i - G_j) (q_i . k_j / sqrt(D))^2     for j <= i, else 0
    y_i   = sum_j A_ij v_j / (sum_j A_ij + eps)        (query head h reads
            K/V head h // (nH / nKV));   Ret = concat_h(y) Wo

then the final norm and the untied head.

Departures from the published model, each a consequence of what the
configuration file states:
- the published ``config.json`` gives neither the power, nor the gate's
  form, nor ``eps``: they are the file's ``assumed`` (power 2; one
  ``log_sigmoid`` gate a K/V head from a linear map WITH a bias; q/k norm
  and rotary as in the Qwen3 parent whose keys the config carries; eps
  1e-6; the 1/sqrt(D));
- depth is the file's ``num_hidden_layers`` (the layers left out lie on
  further chips as pipeline stages);
- no dropout (evaluation).

It reads the parameter tree ``models.brumby.brumby_init`` produces (weights
``[in, out]``, per-layer tensors stacked under ``layers``) and upcasts each
tensor where it is used.  Rows go through the retention in query blocks
(``q_block`` rows against every key), through the FFN in blocks of the same
size, and the head in slices of the vocabulary, so that a 10k-token context
fits beside the engine.  ``sizes`` is the configuration file's dict
(published keys plus ``assumed``).

**What a recurrent implementation has to hold** follows from the same
definition, free of any feature map: ``A_ij = q_i^T (decay k_j k_j^T / D)
q_i``, so after ``n`` tokens a layer and K/V head stand for

    M [D + 1, D, D] = sum_{j < n} exp(G_{n-1} - G_j)
                                  [v_j, 1] (x) (k_j (x) k_j / D)

(``q^T M[d] q``: the numerator's D values and, last, the denominator; the
symmetric ``[D, D]`` faces last, so that they tile), and a further token
takes it to ``g M + [v, 1] (x) k (x) k / D``: ``carry_state``, in float32.
A served page is brought to this form by the program's own ``pair_tensor``.

For tests of the tolerance only: ``cast`` rounds every matrix product's
operands to a narrower type first (what computing in that precision would
give), ``variant`` leaves one piece of the mathematics out (``power1``:
the plain score; ``no_gate``: g = 1; ``no_normaliser``: the numerator alone;
``no_rope``: un-rotated queries and keys), and ``carry_state``'s ``cast``
rounds the carried state to a narrower type on entry and after every token
(what holding the state in that precision would give).
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

_HEAD_SLICES = 128


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, cos, sin):
    """Pairs (i, i + D/2) of the last axis rotated by frequency i."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _in_blocks(fn, rows, block):
    """``fn`` over ``rows [N, ...]`` in blocks of ``block`` rows."""
    n = rows.shape[0]
    out = lax.map(fn, rows.reshape((n // block, block) + rows.shape[1:]))
    return out.reshape((n,) + out.shape[2:])


def carry_state(M, k, v, log_g, cast=None):
    """``M [..., D + 1, D, D]`` carried over the tokens k, v ``[T, ...,
    D]``, log_g ``[T, ...]`` one at a time; rounded to ``cast`` after each
    (and on entry) when given."""
    D = k.shape[-1]

    def rounded(m):
        # (``reduce_precision``: a convert there and back is removed by
        # the TPU compiler, which keeps excess precision)
        if cast is None:
            return m
        kind = jnp.finfo(cast)
        return lax.reduce_precision(m, kind.nexp, kind.nmant)

    def step(m, row):
        k_t, v_t, g_t = row
        v1 = jnp.concatenate([v_t, jnp.ones_like(v_t[..., :1])], -1) / D
        return rounded(
            jnp.exp(g_t)[..., None, None, None] * m
            + v1[..., :, None, None] * k_t[..., None, :, None]
            * k_t[..., None, None, :]), None

    return lax.scan(step, rounded(M), (k, v, log_g))[0]


def forward(params, tokens, sizes: dict, *, out_positions, q_block: int = 128,
            cast=None, variant: str = ""):
    """Logits ``[len(out_positions), vocab]`` (float32) of ONE sequence
    ``tokens [N]`` (N a multiple of ``q_block``; causal: what follows a
    position changes nothing at it)."""
    eps = float(sizes["rms_norm_eps"])
    nH, nKV = int(sizes["num_attention_heads"]), \
        int(sizes["num_key_value_heads"])
    D = int(sizes["head_dim"])
    assumed = sizes["assumed"]
    r_eps = float(assumed["retention_eps"])
    if int(assumed["retention_power"]) != 2:
        raise NotImplementedError("the reference is written for power 2")
    N = tokens.shape[0]
    L = int(sizes["num_hidden_layers"])

    def mm(a, w):
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
        if cast is not None:
            a, w = (t.astype(cast).astype(jnp.float32) for t in (a, w))
        return jnp.dot(a, w, precision=lax.Precision.HIGHEST)

    inv = 1.0 / float(sizes["rope_theta"]) ** (
        jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(N, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    cols = jnp.arange(N)

    def retention(q, k, v, G):
        """q [N, nH, D]; k, v [N, nKV, D]; G [N, nKV] -> [N, nH, D]."""
        kT = k.transpose(1, 2, 0)                               # [c, D, N]
        vT = v.transpose(1, 0, 2)                               # [c, N, D]

        def block(args):
            q_b, G_b, rows = args                  # [B, nH, D], [B, c], [B]
            qg = q_b.reshape(-1, nKV, nH // nKV, D).transpose(1, 2, 0, 3)
            if cast is not None:
                qg, keys = (t.astype(cast).astype(jnp.float32)
                            for t in (qg, kT))
            else:
                keys = kT
            s = jnp.einsum("chbd,cdn->chbn", qg, keys,
                           precision=lax.Precision.HIGHEST) / math.sqrt(D)
            keep = cols[None, :] <= rows[:, None]               # [B, N]
            decay = jnp.exp(jnp.where(
                keep[None], G_b.T[:, :, None] - G.T[:, None, :], -jnp.inf))
            A = decay[:, None] * (s if variant == "power1" else s * s)
            num = jnp.einsum("chbn,cnd->chbd", A, vT,
                             precision=lax.Precision.HIGHEST)
            den = 1.0 if variant == "no_normaliser" \
                else A.sum(-1, keepdims=True) + r_eps
            return (num / den).transpose(2, 0, 1, 3).reshape(-1, nH, D)

        return lax.map(block, (
            q.reshape(N // q_block, q_block, nH, D),
            G.reshape(N // q_block, q_block, nKV),
            cols.reshape(N // q_block, q_block))).reshape(N, nH, D)

    x = params["embed"][tokens].astype(jnp.float32)
    for l in range(L):
        p = {name: w[l] for name, w in params["layers"].items()}
        h = _rms(x, p["input_norm"], eps)
        q = _rms(mm(h, p["wq"]).reshape(N, nH, D), p["q_norm"], eps)
        k = _rms(mm(h, p["wk"]).reshape(N, nKV, D), p["k_norm"], eps)
        v = mm(h, p["wv"]).reshape(N, nKV, D)
        if variant != "no_rope":
            q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        log_g = jax.nn.log_sigmoid(mm(h, p["wg"])
                                   + p["bg"].astype(jnp.float32))
        if variant == "no_gate":
            log_g = jnp.zeros_like(log_g)
        y = retention(q, k, v, jnp.cumsum(log_g, axis=0))
        x = x + mm(y.reshape(N, nH * D), p["wo"])

        def ffn(rows, p=p):
            g = _rms(rows, p["post_norm"], eps)
            return mm(jax.nn.silu(mm(g, p["mlp_gate"]))
                      * mm(g, p["mlp_up"]), p["mlp_down"])
        x = x + _in_blocks(ffn, x, q_block)

    h = _rms(x[out_positions], params["final_norm"], eps)
    head = params["lm_head"]
    V = head.shape[0]
    width = -(-V // _HEAD_SLICES)
    head = jnp.pad(head, ((0, width * _HEAD_SLICES - V), (0, 0)))
    logits = lax.map(lambda w: mm(h, w.T),
                     head.reshape(_HEAD_SLICES, width, -1))
    return logits.transpose(1, 0, 2).reshape(h.shape[0], -1)[:, :V]
