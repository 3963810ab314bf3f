"""The plain reference of the ``minicpm_sala`` family: the forward pass as
the equations state it, in ``jax.numpy``, float32, every product at
``jax.default_matmul_precision("highest")``; no kernel, no cache, no
batching, nothing imported from the program.  Weights are the program's
(bf16) upcast.

``h [T, H]``; ``c = scale_depth / sqrt(published depth)``:

    x = RMSNorm(h); h += c * mixer(x); z = RMSNorm(h);
    h += c * W_down(silu(W_gate z) * (W_up z))

embedding x ``scale_emb``; final RMS norm, untied head, logits / (hidden /
``dim_model_base``).

``minicpm4`` (sparse): q = N(W_q x) [T, nH, D], k = N(W_k x) [T, nKV, D], v
= W_v x; no rotary; query head i reads K/V head ``i // group``.  Pooled keys
``c_j = mean(k[s j .. s j + w - 1])`` (``w = 2 s``: MiniCPM4's
``kernel_size`` 32, ``kernel_stride`` 16), visible to the query at t once
``s j + w - 1 <= t``.  For the query at t (n = t + 1, newest block ``b_t = t
// block``): every block where ``n <= dense_len``; else ``a[i, j] =
softmax_j(q_i . c_j / sqrt(D))`` over the visible j; ``r[g, j]`` its sum
over the heads of K/V head g; ``B[g, b] = max r[g, j]`` over the visible j
whose windows touch block b (``j = R b - 1 .. R b + R - 1 + 1``, R = block /
s: a ``max_pool1d`` of kernel R + 1, stride R, padding 1), 0 where none is;
``+inf`` for block 0 .. ``init_blocks - 1`` and the ``window / block`` newest;
the ``topk`` largest (ties to the lower block).  Softmax attention over the
keys ``s <= t`` of the chosen blocks; ``o *= sigmoid(W_g x)``; ``y = W_o o``.
Computed a block of query rows at a time so that it fits.

``lightning-attn``: q, k = rotary(N(W_q x)), rotary(N(W_k x)), v = W_v x [T,
nh, d]; in its QUADRATIC form ``o_t = sum_{s <= t} lam_h^(t - s) (q_t . k_s)
v_s / sqrt(d)``, ``lam_h = exp(-2^(-8 (h + 1) / nh))``; RMS norm over each
head's d outputs; ``o *= sigmoid(W_g x)``; ``y = W_o o``.  ``recurrent=True``
runs ``S_t = lam S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(d)`` token by
token instead (what the fault ``bf16_state`` rounds).

``fault`` names one of the runner's WRONG models: ``dense`` (no selection),
``no_forced`` (the initial and newest blocks not forced), ``top_less`` (topk
- 1), ``stale_ck`` (pooled key j stands where j + 1 belongs: stale by one
window), ``decay_shift`` (head h's decay given to head h + 1), ``bf16_state``
(the Lightning state held in bfloat16), ``no_gates`` (both gates 1).
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
FAULTS = ("dense", "no_forced", "top_less", "stale_ck", "decay_shift",
          "bf16_state", "no_gates")
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def fault_code(fault):
    """``fault`` as the int the functions below compare: a name of
    ``FAULTS`` (or None: -1), or already an int32 scalar, possibly TRACED, so
    that one compiled reference serves the true model and every control."""
    if fault is None:
        return -1
    return FAULTS.index(fault) if isinstance(fault, str) else fault


def _on(fault, name):
    return fault == FAULTS.index(name)


def sparse_sizes(sizes):
    sc = (sizes.get("assumed") or {}).get("sparse_config") or {}
    return {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
            "topk": 64, "window_size": 2048, "init_blocks": 1,
            "dense_len": 8192, **sc}


def _norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * w.astype(F32)


def _heads(y, n):
    return y.reshape(y.shape[:-1] + (n, y.shape[-1] // n))


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def decay(sizes):
    nh = int(sizes["lightning_nh"])
    return jnp.exp(-jnp.exp2(-8.0 * (jnp.arange(nh, dtype=F32) + 1) / nh))


def pooled_keys(k, sc, stale=False):
    """k [T, nKV, D] -> c [J, nKV, D], ``J = T / s - 1`` (T a multiple of
    s): ``c_j = mean(k[s j .. s j + 2 s - 1])``."""
    s = sc["kernel_stride"]
    halves = k.reshape((k.shape[0] // s, s) + k.shape[1:]).sum(axis=1)
    c = (halves[:-1] + halves[1:]) / (2 * s)
    return jnp.where(stale, jnp.concatenate([c[:1], c[:-1]]), c)


def block_scores(q, c, pos, sc, group, nb, forced=True):
    """B [rows, nKV, nb] of query rows q [rows, nH, D] at ``pos`` [rows]
    against the pooled keys c [J, nKV, D]."""
    s, w, bs = sc["kernel_stride"], sc["kernel_size"], sc["block_size"]
    R = bs // s
    J, nKV, D = c.shape
    qg = q.reshape(q.shape[0], nKV, group, D)
    a = jnp.einsum("tgmd,jgd->tgmj", qg, c) / math.sqrt(D)
    j = jnp.arange(J)
    seen = (s * j[None] + w - 1 <= pos[:, None])[:, None, None]
    a = jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
    r = jnp.where(seen, a, 0.0).sum(axis=2)                 # [rows, nKV, J]
    # the pooled keys whose windows touch block b: j = R b - 1 .. R b + R
    cand = R * jnp.arange(nb)[:, None] - 1 + jnp.arange(R + 1)[None]
    ok = (cand >= 0) & (cand < J)
    got = jnp.where(ok, r[..., jnp.clip(cand, 0, J - 1)], 0.0)
    score = got.max(axis=-1)                                # [rows, nKV, nb]
    b = jnp.arange(nb)[None]
    newest = (pos // bs)[:, None]
    force = ((b < sc["init_blocks"])
             | (b > newest - sc["window_size"] // bs)) & forced
    score = jnp.where(force[:, None], jnp.inf, score)
    return jnp.where((b <= newest)[:, None], score, -1.0)


def chosen_mask(score, pos, sc, less=False):
    """[rows, nKV, nb] bool from ``block_scores``'s: every block up to the
    newest where ``pos + 1 <= dense_len``, else the ``topk`` largest
    (``less``: one fewer)."""
    nb = score.shape[-1]
    b = jnp.arange(nb)[None, None]
    newest = (pos // sc["block_size"])[:, None, None]
    k = min(sc["topk"], nb)
    _, top = lax.top_k(score, k)
    top = jnp.where((jnp.arange(k) == k - 1) & less, -1, top)
    picked = (top[..., None] == b[..., None, :]).any(axis=-2)
    dense = (pos + 1 <= sc["dense_len"])[:, None, None]
    return jnp.where(dense, True, picked) & (b <= newest)


def _sparse_mixer(p, x, sizes, sc, q_block, fault, given, probe):
    nH, nKV = (int(sizes["num_attention_heads"]),
               int(sizes["num_key_value_heads"]))
    eps = float(sizes["rms_norm_eps"])
    T = x.shape[0]
    bs = sc["block_size"]
    nb = -(-T // bs)
    q = _norm(_heads(x @ p["wq"].astype(F32), nH), p["q_norm"], eps)
    k = _norm(_heads(x @ p["wk"].astype(F32), nKV), p["k_norm"], eps)
    v = _heads(x @ p["wv"].astype(F32), nKV)
    D = q.shape[-1]
    c = pooled_keys(k, sc, stale=_on(fault, "stale_ck"))
    key_block = jnp.arange(T) // bs

    def rows_of(args):
        q_b, pos_b, given_b = args
        score = block_scores(q_b, c, pos_b, sc, nH // nKV, nb,
                             forced=jnp.logical_not(
                                 _on(fault, "no_forced")))
        mask = chosen_mask(score, pos_b, sc, less=_on(fault, "top_less"))
        mask = jnp.where(
            _on(fault, "dense"),
            jnp.arange(nb)[None, None] <= (pos_b // bs)[:, None, None], mask)
        if given_b is not None:
            mask = given_b
        qg = q_b.reshape(q_b.shape[0], nKV, nH // nKV, D)
        sc_ = jnp.einsum("tgmd,sgd->tgms", qg, k) / math.sqrt(D)
        ok = mask[:, :, key_block] \
            & (jnp.arange(T)[None, None] <= pos_b[:, None, None])
        w = jax.nn.softmax(jnp.where(ok[:, :, None], sc_, -jnp.inf), axis=-1)
        o = jnp.einsum("tgms,sgd->tgmd", w, v)
        return o.reshape(q_b.shape[0], nH * D), score, mask

    split = lambda a: a.reshape((T // q_block, q_block) + a.shape[1:])  # noqa: E731
    o, score, mask = lax.map(
        rows_of, (split(q), split(jnp.arange(T)),
                  None if given is None else split(given)))
    o = o.reshape(T, nH * D)
    o = o * jnp.where(_on(fault, "no_gates"), 1.0,
                      jax.nn.sigmoid(x @ p["wg"].astype(F32)))
    rows = slice(None) if probe is None else probe
    return o @ p["wo"].astype(F32), dict(
        scores=score.reshape(T, nKV, nb)[rows],
        chosen=mask.reshape(T, nKV, nb)[rows], q=q[rows], pooled=c)


def lightning_qkv(p, x, sizes):
    nh = int(sizes["lightning_nh"])
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    pos = jnp.arange(x.shape[0])
    q = _rope(_norm(_heads(x @ p["wq"].astype(F32), nh), p["q_norm"], eps),
              pos, theta)
    k = _rope(_norm(_heads(x @ p["wk"].astype(F32), nh), p["k_norm"], eps),
              pos, theta)
    return q, k, _heads(x @ p["wv"].astype(F32), nh)


def state_at(k, v, lam, t):
    """``S_t [nh, d, d] = sum_{s <= t} lam^(t - s) k_s^T v_s``."""
    s = jnp.arange(k.shape[0])
    w = jnp.where((s <= t)[:, None], jnp.exp(
        jnp.maximum(t - s, 0)[:, None].astype(F32) * jnp.log(lam)[None]),
        0.0)                                                    # [T, nh]
    return jnp.einsum("sh,shn,shp->hnp", w, k, v)


def _lightning_mixer(p, x, sizes, q_block, fault, recurrent, state_t):
    nh = int(sizes["lightning_nh"])
    d = int(sizes["lightning_head_dim"])
    eps = float(sizes["rms_norm_eps"])
    T = x.shape[0]
    q, k, v = lightning_qkv(p, x, sizes)
    lam = decay(sizes)
    lam = jnp.where(_on(fault, "decay_shift"), jnp.roll(lam, 1), lam)
    low = _on(fault, "bf16_state")
    at = -1 if state_t is None else state_t

    def token_by_token(_):
        def step(carry, row):
            S, kept = carry
            i, q_t, k_t, v_t = row
            S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
            S = jnp.where(low, lax.reduce_precision(S, 8, 7), S)
            return (S, jnp.where(i == at, S, kept)), \
                jnp.einsum("hn,hnp->hp", q_t, S)
        zero = jnp.zeros((nh, d, d), F32)
        (_, kept), o = lax.scan(step, (zero, zero),
                                (jnp.arange(T), q, k, v))
        return o, kept

    def quadratic(_):
        s_idx = jnp.arange(T)

        def rows_of(args):
            q_b, pos_b = args
            gap = (pos_b[:, None] - s_idx[None]).astype(F32)     # [qb, T]
            w = jnp.where(gap >= 0, jnp.exp(
                jnp.maximum(gap, 0.0)[None] * jnp.log(lam)[:, None, None]),
                0.0)                                             # [nh,qb,T]
            qk = jnp.einsum("thn,shn->hts", q_b, k)
            return jnp.einsum("hts,shp->thp", qk * w, v)
        o = lax.map(rows_of, (q.reshape(T // q_block, q_block, nh, d),
                              s_idx.reshape(T // q_block, q_block)))
        return o.reshape(T, nh, d), state_at(k, v, lam, jnp.maximum(at, 0))

    if recurrent:
        o, S = token_by_token(None)
    elif isinstance(fault, int):
        o, S = (token_by_token if low else quadratic)(None)
    else:
        o, S = lax.cond(low, token_by_token, quadratic, None)
    o = _norm(o / math.sqrt(d), p["o_norm"], eps).reshape(T, nh * d)
    o = o * jnp.where(_on(fault, "no_gates"), 1.0,
                      jax.nn.sigmoid(x @ p["wg"].astype(F32)))
    return o @ p["wo"].astype(F32), (None if state_t is None else S)


def _mlp(p, z, rows):
    def part(z_b):
        g = z_b @ p["w_gate"].astype(F32)
        u = z_b @ p["w_up"].astype(F32)
        return (jax.nn.silu(g) * u) @ p["w_down"].astype(F32)
    T = z.shape[0]
    rows = math.gcd(rows, T)
    return lax.map(part, z.reshape(T // rows, rows, -1)).reshape(T, -1)


def hidden(params, tokens, sizes, *, q_block=256, fault=None,
           recurrent=False, state_t=None, given=None, probe=None):
    """(h [T, H] before the final norm, {"sparse": a list a sparse layer of
    {scores, chosen [rows, nKV, nb], q [rows, nH, D], pooled [J, nKV, D]};
    "states": a list a Lightning layer of S at ``state_t`` or None}).
    ``probe``: the rows whose scores, chosen sets and queries are returned
    (None: all); ``given``: chosen sets to use in place of the reference's
    own, a list a sparse layer of [T, nKV, nb] bool.  T must be a multiple
    of ``q_block`` and of the sparse stride (pad the tokens: causal)."""
    fault = fault_code(fault)
    with jax.default_matmul_precision("highest"):
        sc = sparse_sizes(sizes)
        eps = float(sizes["rms_norm_eps"])
        depth = int((sizes.get("published") or {}).get(
            "num_hidden_layers", sizes["num_hidden_layers"]))
        c = float(sizes["scale_depth"]) / math.sqrt(depth)
        h = params["embed"][tokens].astype(F32) * float(sizes["scale_emb"])
        out = {"sparse": [], "states": []}
        for p, kind in zip(params["layers"], sizes["mixer_types"]):
            x = _norm(h, p["input_norm"], eps)
            if kind == SPARSE:
                i = len(out["sparse"])
                y, seen = _sparse_mixer(
                    p, x, sizes, sc, q_block, fault,
                    None if given is None else given[i], probe)
                out["sparse"].append(seen)
            else:
                y, S = _lightning_mixer(p, x, sizes, q_block, fault,
                                        recurrent, state_t)
                out["states"].append(S)
            h = h + c * y
            z = _norm(h, p["post_norm"], eps)
            h = h + c * _mlp(p, z, 1024)
        return h, out


def logits_of(params, h, sizes, out_positions):
    with jax.default_matmul_precision("highest"):
        rows = _norm(h[out_positions], params["final_norm"],
                     float(sizes["rms_norm_eps"]))
        return rows @ params["lm_head"].astype(F32).T \
            * (float(sizes["dim_model_base"]) / float(sizes["hidden_size"]))


def forward(params, tokens, sizes, out_positions, **kw):
    """(fp32 logits [len(out_positions), V], ``hidden``'s extras, probed at
    ``out_positions`` unless ``probe`` says otherwise)."""
    kw.setdefault("probe", out_positions)
    h, extras = hidden(params, tokens, sizes, **kw)
    return logits_of(params, h, sizes, out_positions), extras
