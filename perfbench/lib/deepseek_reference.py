"""The ``deepseek_v3`` forward pass (DeepSeek-V3, GigaChat3 Ultra) in
plain float32 ``jax.numpy``: the reference the served logits are held to.

No kernels, no cache, no batching, expanded attention only (per-head K and
V rebuilt from the latent for every position), a loop over experts; every
matrix product at ``highest`` precision.  It follows the HF
``modeling_deepseek_v3`` equations:

- attention: ``cq = norm(h Wqa)``; ``[q_nope | q_rope] = cq Wqb`` per head;
  ``[ckv | k_rope] = h Wkva``, ``ckv = norm(ckv)``; rotary on ``q_rope`` and
  ``k_rope`` (pairs ``(2i, 2i+1)``, YaRN frequencies), ``k_rope`` shared by
  all heads; ``[k_nope | v] = ckv Wkvb`` per head; scores ``(q_nope . k_nope
  + q_rope . k_rope) * qk_head_dim^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``; causal softmax; ``concat_heads(softmax v) Wo``;
- FFN (dense layers and every expert): ``down(silu(gate x) * up x)``;
- expert layer: ``s = sigmoid(x Wg)``; ``c = s + b``; a group's score is the
  sum of its two largest ``c``; the ``topk_group`` best groups stay, the
  others' ``c`` count as 0; the ``num_experts_per_tok`` largest are chosen;
  weights ``s`` at those / their sum (+1e-20) x ``routed_scaling_factor``;
  plus the shared expert;
- RMS norms ``x * rsqrt(mean(x^2) + eps) * w``; final norm; untied head.

Departures from the published model, each forced by the cut the
configuration file states:
- it is given the same SHARE ``held = (first, count)`` of the routed experts
  as the program: routing is over all ``n_routed_experts``, the experts
  outside the share add nothing (model-configs section 4);
- the vocabulary is the rows the parameter tree holds;
- the multi-token-prediction module is not part of the model's own logits
  and is absent;
- no dropout (evaluation).

It reads the parameter tree ``models.deepseek_v3.deepseek_v3_init``
produces (weights ``[in, out]``, routed experts ``[E_held, F, H]``) and
upcasts each tensor where it is used; attention runs in query blocks and
the experts one at a time, so a 9k-token context fits beside the engine.
``sizes`` is the configuration file's dict (published keys) with
``held``.  ``cast`` (tests of the tolerance only) rounds every matrix
product's operands to a narrower type first: what computing in that
precision would give.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def yarn_inv_freq(sizes: dict) -> np.ndarray:
    rs = sizes["rope_scaling"]
    dim, base = int(sizes["qk_rope_head_dim"]), float(sizes["rope_theta"])
    factor, orig = float(rs["factor"]), \
        int(rs["original_max_position_embeddings"])
    freq = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return freq / factor * ramp + freq * (1 - ramp)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, cos, sin):
    """Pairs (2i, 2i+1) of the last axis rotated by frequency i."""
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def route(x, router, bias, sizes: dict):
    """x [S, H] fp32 -> (ids [S, k], weights [S, k], margin [S]): the
    margin is how far (in ``c``) the routing is from another outcome that
    would change what the HELD experts add: the least distance of a held
    candidate from the top-k boundary, and of the 4th group score from the
    5th."""
    E, n_group, k = (int(sizes["n_routed_experts_published"]),
                     int(sizes["n_group"]),
                     int(sizes["num_experts_per_tok"]))
    first, count = sizes["held"]
    s = jax.nn.sigmoid(x @ router.astype(jnp.float32))
    c = s + bias.astype(jnp.float32)
    gs = lax.top_k(c.reshape(-1, n_group, E // n_group), 2)[0].sum(-1)
    g_sorted = -jnp.sort(-gs, axis=-1)
    tg = int(sizes["topk_group"])
    keep = gs >= g_sorted[:, tg - 1:tg]
    cand = jnp.where(jnp.repeat(keep, E // n_group, axis=1), c, 0.0)
    top, ids = lax.top_k(cand, k + 1)
    w = jnp.take_along_axis(s, ids[:, :k], axis=1)
    if sizes.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * float(sizes["routed_scaling_factor"])
    held = cand[:, first:first + count]
    chosen = held >= top[:, k - 1:k]
    dist = jnp.where(chosen, held - top[:, k:k + 1], top[:, k - 1:k] - held)
    live = jnp.repeat(keep, E // n_group, axis=1)[:, first:first + count]
    margin = jnp.min(jnp.where(live, dist, jnp.inf), axis=-1)
    if tg < n_group:
        margin = jnp.minimum(margin, g_sorted[:, tg - 1] - g_sorted[:, tg])
    return ids[:, :k], w, margin


def forward(params, tokens, sizes: dict, *, out_positions, q_block: int = 256,
            cast=None):
    """tokens int32 [S] -> (logits float32 [len(out_positions), V],
    routing margin [len(out_positions)]: the least over the expert layers
    at that position)."""
    with jax.default_matmul_precision("highest"):
        f32 = (lambda a: a.astype(jnp.float32)) if cast is None else \
            (lambda a: a.astype(cast).astype(jnp.float32))

        def mm(a, b):
            return f32(a) @ f32(b)
        eps = float(sizes["rms_norm_eps"])
        nH = int(sizes["num_attention_heads"])
        dn, dr, dv = (int(sizes["qk_nope_head_dim"]),
                      int(sizes["qk_rope_head_dim"]),
                      int(sizes["v_head_dim"]))
        C = int(sizes["kv_lora_rank"])
        rs = sizes["rope_scaling"]
        m = _mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
        scale = (dn + dr) ** -0.5 * m * m
        att = _mscale(float(rs["factor"]), float(rs["mscale"])) / m
        S = tokens.shape[0]
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
            * jnp.asarray(yarn_inv_freq(sizes), jnp.float32)[None]
        cos, sin = jnp.cos(ang) * att, jnp.sin(ang) * att
        nb = -(-S // q_block)
        pad = nb * q_block - S

        def attention(p, x):
            h = _rms(x, p["input_norm"], eps)
            cq = _rms(mm(h, p["wq_a"]), p["q_norm"], eps)
            q = mm(cq, p["wq_b"]).reshape(S, nH, dn + dr)
            kv = mm(h, p["wkv_a"])
            ckv = _rms(kv[:, :C], p["kv_norm"], eps)
            k_rope = _rope(kv[:, C:], cos, sin)                   # [S, dr]
            q_rope = _rope(q[..., dn:], cos[:, None], sin[:, None])
            kvb = mm(ckv, p["wkv_b"]).reshape(S, nH, dn + dv)
            k_nope, v = kvb[..., :dn], kvb[..., dn:]
            qf = jnp.pad(jnp.concatenate([q[..., :dn], q_rope], -1),
                         ((0, pad), (0, 0), (0, 0)))
            kf = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope[:, None], (S, nH, dr))], -1)
            kf, vf = f32(kf), f32(v)

            def block(i):
                qb = lax.dynamic_slice_in_dim(qf, i * q_block, q_block, 0)
                s = jnp.einsum("qnd,tnd->nqt", f32(qb), kf) * scale
                rows = i * q_block + jnp.arange(q_block)
                s = jnp.where(jnp.arange(S)[None, None, :]
                              <= rows[None, :, None], s, -jnp.inf)
                return jnp.einsum("nqt,tnv->qnv", f32(jax.nn.softmax(s, -1)),
                                  vf)
            o = lax.map(block, jnp.arange(nb)).reshape(nb * q_block,
                                                       nH * dv)[:S]
            return x + mm(o, p["wo"])

        def ffn(x, gate, up, down):
            return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

        def dense_layer(x, p):
            x = attention(p, x)
            h = _rms(x, p["post_norm"], eps)
            return x + ffn(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"]), None

        first, count = sizes["held"]

        def moe_layer(x, p):
            x = attention(p, x)
            h = _rms(x, p["post_norm"], eps)
            ids, w, margin = route(h, p["router"], p["router_bias"], sizes)

            def expert(e, y):
                we = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
                # [F, H] as held: gate/up contract H, down maps F -> H
                g = f32(h) @ f32(p["w_gate"][e]).T
                u = f32(h) @ f32(p["w_up"][e]).T
                return y + we[:, None] * (f32(jax.nn.silu(g) * u)
                                          @ f32(p["w_down"][e]))
            y = lax.fori_loop(0, count, expert, jnp.zeros_like(x))
            y = y + ffn(h, p["shared_gate"], p["shared_up"],
                        p["shared_down"])
            return x + y, margin

        x = params["embed"].astype(jnp.float32)[tokens]
        x, _ = lax.scan(dense_layer, x, params["dense"])
        x, margins = lax.scan(moe_layer, x, params["moe"])       # [Le, S]
        out = jnp.asarray(out_positions, jnp.int32)
        h = _rms(x[out], params["final_norm"], eps)
        return mm(h, params["lm_head"].T), margins.min(axis=0)[out]
