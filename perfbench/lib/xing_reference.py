"""The ``xing4_0`` forward pass (Xing4.0-29B-A4B) in plain float32
``jax.numpy``: the reference the served logits are held to.

The ``deepseek_v3`` layers (latent attention with YaRN rotary positions,
dense SwiGLU, sigmoid routing with a selection bias, a shared expert; the
equations are in ``lib/deepseek_reference.py``, whose norm, rotary, YaRN
and routing functions are imported) on FOUR residual streams mixed by
manifold-constrained hyper-connections (arXiv:2512.24880 over
arXiv:2409.19606).  Per token, with ``X [n, C]`` (n = ``hc_mult``, C =
``hidden_size``), for EACH sublayer F (attention after ``input_norm``; the
dense FFN or the expert layer after ``post_norm``), each with its own
``phi [nC, 2n + n*n]``, ``b [2n + n*n]``, ``alpha [3]``:

    u      = vec(X) * rsqrt(mean(vec(X)^2) + hc_eps)
    H_pre  = sigmoid(alpha_pre * (u phi_pre) + b_pre)                 [n]
    H_post = 2 sigmoid(alpha_post * (u phi_post) + b_post)            [n]
    M      = exp(clip(alpha_res * mat(u phi_res) + b_res,
                      mhc_h_res_clamp_min, mhc_h_res_clamp_max))      [n, n]
    hc_sinkhorn_iters times:  M <- M / (colsum(M) + hc_eps)
                              M <- M / (rowsum(M) + hc_eps)
    h      = sum_j H_pre[j] X[j];   y = F(norm(h))
    X'[i]  = sum_j M[i, j] X[j] + H_post[i] y

``X_0`` is the embedding copied to the n streams; the final norm and the
head read ``sum_i X_L[i]``.  No kernels, no cache, no batching, expanded
attention in query blocks, experts one at a time, every matrix product at
``highest`` precision; the embedding is gathered before it is upcast and
the head runs in blocks of vocabulary rows, so a 6k-token teacher-forced
row fits beside 9.6 GB of weights.

Departures from the published model:
- what ``config.json`` does not fix and the configuration file lists under
  ``assumed`` (a later reading of the modeling code may disagree with any of
  them): the maps wrap attention and FFN SEPARATELY, two sets a layer; the
  norm over the nC values has NO GAIN of its own (one folds into ``phi``
  exactly); ``hc_eps`` is the epsilon of that norm AND of every Sinkhorn
  division; COLUMNS are normalised before rows; the CLAMP is applied to the
  res logits ahead of ``exp``; expansion by COPYING and collapse by SUMMING;
  ``phi``, ``b``, ``alpha`` held in fp32; rotary pairs ``(2i, 2i+1)``
  (``rope_interleave``) and ``initializer_range`` 0.02 as for the other
  ``deepseek_v3`` configuration;
- the multi-token-prediction module takes no part in the model's own
  logits and is absent; no dropout (evaluation).

It reads the parameter tree ``models.deepseek_v3.deepseek_v3_init``
produces for a config with ``hc_mult`` and upcasts each tensor where it is
used.  ``sizes`` is the configuration file's dict (published keys) with
``held`` and ``n_routed_experts_published``.  For tests of the tolerance
only: ``cast`` rounds every matrix product's operands to a narrower type
first (what computing in that precision would give); ``fault`` computes a
WRONG model — ``"res_identity"``: ``H_res`` = I (the streams never mix),
``"sinkhorn_once"``: one Sinkhorn iteration of the twenty.
"""
import jax
import jax.numpy as jnp
from jax import lax

from perfbench.lib.deepseek_reference import (_mscale, _rms, _rope, route,
                                              yarn_inv_freq)

FAULTS = (None, "res_identity", "sinkhorn_once")


def residual_maps(X, phi, b, alpha, sizes: dict, fault=None):
    """X [S, n, C] fp32 -> (H_pre [S, n], H_post [S, n], H_res [S, n, n]),
    as the module docstring reads."""
    S, n, C = X.shape
    eps = float(sizes["hc_eps"])
    x = X.reshape(S, n * C)
    u = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    t = u @ phi.astype(jnp.float32)
    b = b.astype(jnp.float32)
    pre = jax.nn.sigmoid(alpha[0] * t[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * t[:, n:2 * n] + b[n:2 * n])
    logits = (alpha[2] * t[:, 2 * n:] + b[2 * n:]).reshape(S, n, n)
    M = jnp.exp(jnp.clip(logits, float(sizes["mhc_h_res_clamp_min"]),
                         float(sizes["mhc_h_res_clamp_max"])))
    iters = 1 if fault == "sinkhorn_once" else int(sizes["hc_sinkhorn_iters"])
    for _ in range(iters):
        M = M / (M.sum(axis=1, keepdims=True) + eps)    # columns: over i
        M = M / (M.sum(axis=2, keepdims=True) + eps)    # rows: over j
    if fault == "res_identity":
        M = jnp.broadcast_to(jnp.eye(n, dtype=jnp.float32), M.shape)
    return pre, post, M


def hidden(params, tokens, sizes: dict, *, out_positions, q_block: int = 256,
           cast=None, fault=None):
    """tokens int32 [S] -> (the final norm's output float32
    [len(out_positions), H], routing margin [len(out_positions)]: the
    least over the expert layers at that position)."""
    assert fault in FAULTS, fault
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
        n = int(sizes["hc_mult"])
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

        def attention(p, h):
            """What the attention adds for its normed input h [S, H]."""
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
            return mm(o, p["wo"])

        def ffn(x, gate, up, down):
            return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)

        first, count = sizes["held"]

        # The routed experts stay in their stack [Le, E, F, H] and one
        # expert's matrices are taken from it at a time: a layer's worth
        # sliced out for the scan would be a 1.4 GB copy beside the engine.
        stack = {k: params["moe"][k] for k in ("w_gate", "w_up", "w_down")}

        def experts(p, layer, h):
            """What expert layer ``layer`` adds for its normed input h:
            (y, routing margin [S])."""
            ids, w, margin = route(h, p["router"], p["router_bias"], sizes)

            def expert(e, y):
                we = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
                # [F, H] as held: gate/up contract H, down maps F -> H
                g = f32(h) @ f32(stack["w_gate"][layer, e]).T
                u = f32(h) @ f32(stack["w_up"][layer, e]).T
                return y + we[:, None] * (f32(jax.nn.silu(g) * u)
                                          @ f32(stack["w_down"][layer, e]))
            y = lax.fori_loop(0, count, expert, jnp.zeros_like(h))
            return y + ffn(h, p["shared_gate"], p["shared_up"],
                           p["shared_down"]), margin

        def sublayer(p, sub, norm, X, F):
            """One sublayer on the n streams: X [S, n, C] -> (X', what F
            returned beside its output)."""
            pre, post, res = residual_maps(
                X, p[f"hc_{sub}_phi"], p[f"hc_{sub}_b"],
                p[f"hc_{sub}_alpha"], sizes, fault)
            h = jnp.einsum("sj,sjc->sc", pre, X)
            y, aux = F(_rms(h, p[norm], eps))
            return jnp.einsum("sij,sjc->sic", res, X) \
                + post[:, :, None] * y[:, None, :], aux

        def layer(X, p, F):
            X, _ = sublayer(p, "attn", "input_norm", X,
                            lambda h: (attention(p, h), None))
            return sublayer(p, "ffn", "post_norm", X, F)

        def dense_layer(X, p):
            return layer(X, p, lambda h: (ffn(
                h, p["mlp_gate"], p["mlp_up"], p["mlp_down"]), None))

        def moe_layer(X, p_layer):
            p, l = p_layer
            return layer(X, p, lambda h: experts(p, l, h))

        x = params["embed"][tokens].astype(jnp.float32)
        X = jnp.broadcast_to(x[:, None, :], (S, n, x.shape[-1]))
        X, _ = lax.scan(dense_layer, X, params["dense"])
        rest = {k: v for k, v in params["moe"].items() if k not in stack}
        X, margins = lax.scan(
            moe_layer, X, (rest, jnp.arange(stack["w_gate"].shape[0])))
        out = jnp.asarray(out_positions, jnp.int32)
        h = _rms(X[out].sum(axis=1), params["final_norm"], eps)
        return h, margins.min(axis=0)[out]


def _head_blocks(params, v_block: int):
    """The head's rows as [blocks, v_block, H] (it divides: 131,072 =
    1,024 x 128 published)."""
    head = params["lm_head"]
    assert head.shape[0] % v_block == 0, (head.shape, v_block)
    return head.reshape(head.shape[0] // v_block, v_block, head.shape[1])


def logits_of(params, h, *, v_block: int = 8192, cast=None):
    """h float32 [N, H] -> logits float32 [N, V], the head upcast a block
    of rows at a time."""
    f32 = (lambda a: a.astype(jnp.float32)) if cast is None else \
        (lambda a: a.astype(cast).astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        out = lax.map(lambda w: f32(h) @ f32(w).T,
                      _head_blocks(params, v_block))     # [blocks, N, vb]
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1)


def forward(params, tokens, sizes: dict, *, out_positions, q_block: int = 256,
            cast=None, fault=None, v_block: int = 8192):
    """tokens int32 [S] -> (logits float32 [len(out_positions), V], routing
    margin [len(out_positions)])."""
    h, margin = hidden(params, tokens, sizes, out_positions=out_positions,
                       q_block=q_block, cast=cast, fault=fault)
    v_block = min(v_block, params["lm_head"].shape[0])
    return logits_of(params, h, v_block=v_block, cast=cast), margin


def token_gaps(params, h, next_tokens, *, vocab: int, v_block: int = 8192):
    """For teacher-forced positions with final hidden ``h [N, H]`` and the
    token that FOLLOWED each (``next_tokens [N]``): the reference's largest
    logit over the first ``vocab`` rows minus that token's logit, float32
    [N] — without ever holding N x V logits."""
    with jax.default_matmul_precision("highest"):
        blocks = _head_blocks(params, v_block)

        def one(carry, xs):
            best, picked = carry
            w, base = xs
            lg = h @ w.astype(jnp.float32).T                    # [N, vb]
            ids = base + jnp.arange(v_block)
            lg = jnp.where(ids[None] < vocab, lg, -jnp.inf)
            mine = jnp.where(ids[None] == next_tokens[:, None], lg, -jnp.inf)
            return (jnp.maximum(best, lg.max(-1)),
                    jnp.maximum(picked, mine.max(-1))), None
        start = jnp.full((h.shape[0],), -jnp.inf, jnp.float32)
        (best, picked), _ = lax.scan(
            one, (start, start),
            (blocks, jnp.arange(blocks.shape[0]) * v_block))
    return best - picked
