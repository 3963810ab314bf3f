"""The ``kimi_linear`` forward pass (moonshotai Kimi-Linear-48B-A3B-Instruct)
in plain float32 ``jax.numpy``: the reference the served logits and state
pages are held to.

No kernels, no cache, no batching, no chunked form: the delta rule runs TOKEN
BY TOKEN (``lax.scan`` over the positions), latent attention is expanded
(per-head K and V rebuilt from the latent row for every position) full causal
softmax, a loop over experts, every matrix product at ``highest`` precision.
Written from the ``kimi_linear`` modeling code, from memory (no network
here); what ``config.json`` does not settle is listed under ``assumed`` in
the configuration file.  ``RMS(h; w) = h rsqrt(mean(h^2) + eps) w``.  Block
``l`` (1-based):

    h <- h + Mixer_l(RMS(h; input_norm));  h <- h + FFN_l(RMS(h; post_norm))

- ``Mixer_l`` for ``l`` in ``linear_attn_config.kda_layers`` (nh heads of d =
  ``linear_attn_config.head_dim``): ``[q~ | k~ | v~] = x W_qkv`` (three
  projections, held side by side); each through its own depthwise causal
  filter of ``short_conv_kernel_size`` taps (zeros before position 0, the
  LAST tap on the current token, no bias), then SiLU; ``q = q / |q|_head *
  d^-0.5``, ``k = k / |k|_head`` (``|.|`` = sqrt(sum of squares + 1e-6));
  ``g = -exp(A_log_h) softplus((x W_f_down) W_f_up + dt_bias)`` a channel;
  ``beta = sigmoid(x W_beta)`` a head;  ``S' = Diag(e^g) S_{t-1}``, ``S_t =
  S' + beta k (v - S'^T k)^T``, ``o = S_t^T q``;  ``y = RMS_head(o; o_norm) .
  sigmoid((x W_g_down) W_g_up)``; ``y W_o``;
- ``Mixer_l`` for ``l`` in ``full_attn_layers``: ``q = x W_q`` as ``[nH, nope
  + rope]``; ``[c | k_pe] = x W_kv_a``, ``c = RMS(c; kv_norm)``; NO rotation
  (``mla_use_nope``); ``[k_nope | v] = c W_kv_b`` a head; scores ``q . [k_nope
  | k_pe] (nope + rope)^-0.5``; causal softmax; ``concat(v) W_o``;
- ``FFN_l``: ``down(silu(gate x) * up x)`` of ``intermediate_size`` for ``l
  <= first_k_dense_replace``; else ``s = sigmoid(x W_r)``, chosen by ``s +
  b``, the ``num_experts_per_token`` largest of ``num_experts`` (one group),
  weights ``s`` at those / their sum (+1e-20) x ``routed_scaling_factor``,
  plus the shared expert;
- ``h_0 = embed[tokens]``; at the end ``RMS(.; final_norm)`` and the untied
  head.

A stream's state of a KDA layer at position t is ``S_t [nh, d_k, d_v]`` and
the rows ``t - taps + 2 .. t`` of ``[q~ | k~ | v~]`` BEFORE the filter.

Departures from the published model, each forced by the cut the configuration
file states: the DEPTH is ``num_hidden_layers`` of the file (the layer lists
are read up to it); it is given the same SHARE ``held = (first, count)`` of
the routed experts as the program (routing is over all ``num_experts``, the
experts outside the share add nothing); the vocabulary is the rows the
parameter tree holds; no dropout (evaluation).

It reads the parameter tree ``models.kimi_linear.kimi_linear_init`` produces
(weights ``[in, out]``, routed experts ``[E_held, F, H]``, one dict a layer)
and upcasts each tensor where it is used; every per-row product runs in row
blocks and attention in query blocks, so that a 16k-token document fits
beside the engine.  ``sizes`` is the configuration file's dict.

Switches used ONLY for the controls that show the comparison can fail
(``fault``): ``"no_delta"`` drops the correction (``S <- Diag(alpha) S + beta
k v^T``); ``"head_decay"`` reads one decay a head (the mean of ``g`` over the
head's channels); ``"unit_alpha"`` reads ``alpha = 1``; ``"rotary_on"``
rotates q's last ``qk_rope_head_dim`` columns and ``k_pe`` (``rope_theta``,
pairs ``(2i, 2i+1)``); ``"bf16_state"`` rounds the state to bfloat16 after
every token.  A switch is given by name or as ``fault_code``'s number, which
may be TRACED: one compiled program then reads every wrong model and, at 0,
the true one (a select between both readings; the true reading's arithmetic
is what it is without the switch).  ``zero_state_at`` = P (a traced scalar;
0 changes nothing) makes rows ``t >= P`` read a state and filter rows that
hold nothing of the rows before P: what a stream that resumed at P WITHOUT
its snapshot would compute.  ``first_layer_steps`` (what layer 1's
recurrence consumes at each token, from the embedding, the norm, the
projections, the filters and the gates alone) and ``carry_state`` (the
recurrence over them from a given state) hold the state's OWN arithmetic
apart from everything upstream of it.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ROW_BLOCK = 512
KDA_BLOCK = 2048
HEAD_SLICES = 8
L2_EPS = 1e-6
FAULTS = ("no_delta", "head_decay", "unit_alpha", "rotary_on", "bf16_state")


def fault_code(fault):
    """0 for the true model, 1 + its place in ``FAULTS`` for a switch by
    name; a number (traced or not) as it is."""
    if fault is None:
        return 0
    return FAULTS.index(fault) + 1 if isinstance(fault, str) else fault


def _switched(fault, name, wrong, true):
    """``wrong`` where ``fault`` is the switch ``name``, else ``true``."""
    on = fault_code(fault) == FAULTS.index(name) + 1
    if isinstance(on, bool):
        return wrong if on else true
    return jnp.where(on, wrong, true)


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * _f32(w)


def _rows(fn, x, block=ROW_BLOCK):
    """``fn`` over the rows of ``x [S, ...]`` in blocks (a tree of ``[S,
    ...]`` results)."""
    S = x.shape[0]
    n = -(-S // block)
    xb = jnp.pad(x, ((0, n * block - S),) + ((0, 0),) * (x.ndim - 1)) \
        .reshape((n, block) + x.shape[1:])
    return jax.tree_util.tree_map(
        lambda a: a.reshape((n * block,) + a.shape[2:])[:S],
        lax.map(fn, xb))


def _rounded(a, dtype):
    """``a`` held in ``dtype`` (``reduce_precision``: a convert there and
    back may be removed by a compiler that keeps excess precision)."""
    if dtype is None:
        return a
    kind = jnp.finfo(dtype)
    return lax.reduce_precision(a, kind.nexp, kind.nmant)


def layer_kinds(sizes: dict):
    """"kda" / "latent" of layers 1 .. ``num_hidden_layers``."""
    full = set(sizes["linear_attn_config"]["full_attn_layers"])
    return ["latent" if l in full else "kda"
            for l in range(1, int(sizes["num_hidden_layers"]) + 1)]


def _kda_dims(sizes):
    lin = sizes["linear_attn_config"]
    return (int(lin["num_heads"]), int(lin["head_dim"]),
            int(lin["short_conv_kernel_size"]))


def _unit(a):
    return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)


def delta_step(S, q, k, v, g, beta, fault=None):
    """One token of one stream: S ``[nh, dk, dv]``, q / k / g ``[nh, dk]``, v
    ``[nh, dv]``, beta ``[nh]`` -> (o ``[nh, dv]``, S_t)."""
    g = _switched(fault, "head_decay",
                  jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), g)
    S = _switched(fault, "unit_alpha", S, jnp.exp(g)[..., None] * S)
    r = _switched(fault, "no_delta", 0.0, jnp.einsum("hkv,hk->hv", S, k))
    S = S + k[..., None] * (beta[:, None] * (v - r))[:, None, :]
    S = _switched(fault, "bf16_state", _rounded(S, jnp.bfloat16), S)
    return jnp.einsum("hkv,hk->hv", S, q), S


def _kda_steps(p, u, sizes, before=None, start=0, cut=None,
               held=lambda a: a):
    """What a KDA layer's recurrence consumes at each row of normed ``u [S,
    H]`` (positions ``start ..``): (q, k ``[S, nh, d]``, v, g ``[S, nh, d]``,
    beta ``[S, nh]``, the projected rows ``[taps - 1 + S, 3 nh d]`` with
    ``before`` — the ``taps - 1`` rows ahead of them, zeros by default —
    in front).  ``cut``: see ``hidden`` (the control only).  ``held`` rounds
    where the configuration holds an activation in its dtype."""
    nh, d, taps = _kda_dims(sizes)
    S = u.shape[0]
    qkv = held(_rows(lambda r: r @ _f32(p["w_qkv"]), u))
    if before is None:
        before = jnp.zeros((taps - 1, qkv.shape[1]), jnp.float32)
    padded = jnp.concatenate([before, qkv])              # row t at t + taps-1
    w = _f32(p["conv_w"])                                # [3 nh d, taps]
    rows = start + jnp.arange(S)
    mixed = jnp.zeros_like(qkv)
    for j in range(taps):
        term = padded[j:j + S] * w[:, j]
        if cut is not None:
            src = rows - (taps - 1) + j          # the position tap j reads
            term = jnp.where(((rows >= cut) & (src < cut))[:, None], 0.0,
                             term)
        mixed = mixed + term
    act = held(jax.nn.silu(mixed)).reshape(S, 3, nh, d)
    q, k, v = act[:, 0], act[:, 1], act[:, 2]
    f = held(u @ _f32(p["w_f_down"])) @ _f32(p["w_f_up"]) + _f32(p["dt_bias"])
    g = -jnp.exp(_f32(p["A_log"]))[:, None] * jax.nn.softplus(
        f.reshape(S, nh, d))
    beta = jax.nn.sigmoid(u @ _f32(p["w_beta"]))
    return _unit(q) * d ** -0.5, _unit(k), v, g, beta, padded


def hidden(params, tokens, sizes: dict, *, q_block: int = 128, fault=None,
           zero_state_at=0, state_at=None):
    """tokens int32 [S] -> (the residual stream after the last layer [S, H],
    the least routing margin a position over the expert layers [S], and
    where ``state_at`` = t is given every KDA layer's state at position t:
    (S_t ``[kda layers, nh, dk, dv]``, filter rows ``[kda layers, taps - 1, 3
    nh d]``), else None)."""
    eps = float(sizes["rms_norm_eps"])
    nh, d, taps = _kda_dims(sizes)
    nH = int(sizes["num_attention_heads"])
    dn, dr, dv = (int(sizes["qk_nope_head_dim"]),
                  int(sizes["qk_rope_head_dim"]), int(sizes["v_head_dim"]))
    C = int(sizes["kv_lora_rank"])
    S = tokens.shape[0]
    rows = jnp.arange(S)
    cut = jnp.asarray(zero_state_at, jnp.int32)
    at = None if state_at is None else jnp.asarray(state_at, jnp.int32)
    nb = -(-S // q_block)
    pad = nb * q_block - S

    def kda(p, u):
        """Blocks of ``KDA_BLOCK`` rows at a time (the projected rows of 16k
        tokens are 0.8 GB in float32), the state and the filters' last rows
        carried from block to block; inside a block, token by token."""
        B = min(KDA_BLOCK, -(-S // 128) * 128)
        n = -(-S // B)
        ub = jnp.pad(u, ((0, n * B - S), (0, 0))).reshape(n, B, -1)
        width = 3 * nh * d

        def token(carry, row):
            state, kept = carry
            t, q_t, k_t, v_t, g_t, b_t = row
            state = jnp.where((t == cut) & (cut > 0), 0.0, state)
            o, state = delta_step(state, q_t, k_t, v_t, g_t, b_t, fault)
            if at is not None:
                kept = jnp.where(t == at, state, kept)
            return (state, kept), o

        def block(carry, xb):
            state, kept, tail, kept_rows = carry
            i, u_b = xb
            q, k, v, g, beta, padded = _kda_steps(
                p, u_b, sizes, before=tail, start=i * B, cut=cut)
            (state, kept), o = lax.scan(
                token, (state, kept),
                (i * B + jnp.arange(B), q, k, v, g, beta))
            if at is not None:
                # rows at - taps + 2 .. at = this block's padded rows
                # (at - i B) + 1 .. (at - i B) + taps - 1
                local = jnp.clip(at - i * B, 0, B - 1)
                here = lax.dynamic_slice(padded, (local + 1, 0),
                                         (taps - 1, width))
                kept_rows = jnp.where(at // B == i, here, kept_rows)
            return (state, kept, padded[B:], kept_rows), o
        zero = jnp.zeros((nh, d, d), jnp.float32)
        no_rows = jnp.zeros((taps - 1, width), jnp.float32)
        (_, kept, _, kept_rows), o = lax.scan(
            block, (zero, zero, no_rows, no_rows), (jnp.arange(n), ub))
        o = o.reshape(n * B, nh, d)[:S]
        gate = jax.nn.sigmoid((u @ _f32(p["w_g_down"])) @ _f32(p["w_g_up"]))
        y = _rms(o, p["o_norm"], eps).reshape(S, nh * d) * gate
        m = _rows(lambda r: r @ _f32(p["wo"]), y)
        return m, None if at is None else (kept, kept_rows)

    def rotated(x):
        """(the control only) pairs (2i, 2i+1) of the last axis rotated by
        frequency i at the row's position."""
        inv = float(sizes["rope_theta"]) ** (
            -np.arange(0, dr, 2, dtype=np.float64) / dr)
        ang = rows.astype(jnp.float32)[:, None] \
            * jnp.asarray(inv, jnp.float32)[None]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        if x.ndim == 3:
            cos, sin = cos[:, None], sin[:, None]
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)

    def latent(p, u):
        q = _rows(lambda r: r @ _f32(p["wq"]), u).reshape(S, nH, dn + dr)
        kv = u @ _f32(p["wkv_a"])
        c, k_pe = _rms(kv[:, :C], p["kv_norm"], eps), kv[:, C:]
        q = _switched(fault, "rotary_on", jnp.concatenate(
            [q[..., :dn], rotated(q[..., dn:])], -1), q)
        k_pe = _switched(fault, "rotary_on", rotated(k_pe), k_pe)
        kvb = _rows(lambda r: r @ _f32(p["wkv_b"]), c) \
            .reshape(S, nH, dn + dv)
        kf = jnp.concatenate(
            [kvb[..., :dn], jnp.broadcast_to(k_pe[:, None], (S, nH, dr))], -1)
        vf = kvb[..., dn:]
        qf = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

        def block(i):
            qb = lax.dynamic_slice_in_dim(qf, i * q_block, q_block, 0)
            s = jnp.einsum("qnd,tnd->nqt", qb, kf) * (dn + dr) ** -0.5
            qi = i * q_block + jnp.arange(q_block)
            s = jnp.where(rows[None, None, :] <= qi[None, :, None], s,
                          -jnp.inf)
            return jnp.einsum("nqt,tnv->qnv", jax.nn.softmax(s, -1), vf)
        o = lax.map(block, jnp.arange(nb)).reshape(nb * q_block, nH * dv)[:S]
        return _rows(lambda r: r @ _f32(p["wo"]), o)

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens])
        states, margins = [], [jnp.full((S,), jnp.inf, jnp.float32)]
        dense = int(sizes["first_k_dense_replace"])
        for l, (kind, p) in enumerate(zip(layer_kinds(sizes),
                                          params["layers"])):
            u = _rms(x, p["input_norm"], eps)
            if kind == "kda":
                m, state = kda(p, u)
                states.append(state)
            else:
                m = latent(p, u)
            x = x + m
            h = _rms(x, p["post_norm"], eps)
            if l < dense:
                x = x + _ffn(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
            else:
                y, margin = expert_layer(p, h, sizes)
                x = x + y
                margins.append(margin)
        margin = jnp.stack(margins).min(axis=0)
        if at is None:
            return x, margin, None
        return x, margin, (jnp.stack([s for s, _ in states]),
                           jnp.stack([c for _, c in states]))


def _ffn(h, gate, up, down):
    return _rows(lambda r: (jax.nn.silu(r @ _f32(gate))
                            * (r @ _f32(up))) @ _f32(down), h)


def expert_layer(p, h, sizes: dict):
    """The expert layer of normed ``h [S, H]`` with the share ``sizes["held"]``
    of the routed experts: (what the held experts and the shared expert add,
    the routing margin a row)."""
    ids, w, margin = route(h, p["router"], p["router_bias"], sizes)
    first, count = sizes["held"]

    def expert(e, y):
        we = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)

        def rows_of(r):          # [F, H] as held: gate / up contract H
            return (jax.nn.silu(r @ _f32(p["w_gate"][e]).T)
                    * (r @ _f32(p["w_up"][e]).T)) @ _f32(p["w_down"][e])
        return y + we[:, None] * _rows(rows_of, h)
    y = lax.fori_loop(0, count, expert, jnp.zeros_like(h))
    return y + _ffn(h, p["shared_gate"], p["shared_up"],
                    p["shared_down"]), margin


def route(x, router, bias, sizes: dict):
    """x [S, H] fp32 -> (ids [S, k], weights [S, k], margin [S]): the margin
    is how far (in ``s + b``) the routing is from another outcome that would
    change what the HELD experts add: the least distance of a held expert's
    score from the top-k boundary."""
    k = int(sizes["num_experts_per_token"])
    first, count = sizes["held"]
    s = jax.nn.sigmoid(x @ _f32(router))
    c = s + _f32(bias)
    top, ids = lax.top_k(c, k + 1)
    w = jnp.take_along_axis(s, ids[:, :k], axis=1)
    if sizes.get("moe_renormalize", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * float(sizes["routed_scaling_factor"])
    held = c[:, first:first + count]
    chosen = held >= top[:, k - 1:k]
    dist = jnp.where(chosen, held - top[:, k:k + 1], top[:, k - 1:k] - held)
    return ids[:, :k], w, dist.min(axis=-1)


def first_layer_steps(params, tokens, sizes: dict, *, skip: int = 0,
                      act=None):
    """What LAYER 1's recurrence consumes at each of ``tokens[skip:]``: (q,
    k, v, g, beta), float32, ``highest`` precision, from the embedding,
    ``input_norm``, the projections, the filters and the gates.
    ``tokens[:skip]`` only feed the filters: the ``taps - 1`` tokens before
    the first wanted one (with ``skip`` 0 the first token is position 0 and
    the filters read zeros before it).  ``act``: a dtype; the values are then
    ROUNDED to it where the configuration holds an activation on the way
    into the state (the embedding, the norm's output, the projected rows,
    the filters' output, the gate's inner activation)."""
    def held(a):
        return _rounded(a, act)
    p = params["layers"][0]
    with jax.default_matmul_precision("highest"):
        h = held(_f32(params["embed"][tokens]))
        u = held(_rms(h, p["input_norm"], float(sizes["rms_norm_eps"])))
        return tuple(a[skip:] for a in _kda_steps(p, u, sizes,
                                                  held=held)[:5])


def carry_state(state, q, k, v, g, beta, cast=None, fault=None):
    """``state [nh, dk, dv]`` carried over the tokens q / k / g ``[T, nh,
    dk]``, v ``[T, nh, dv]``, beta ``[T, nh]`` one at a time in float32;
    rounded to ``cast`` after each (and on entry) when given."""
    def step(s, row):
        return _rounded(delta_step(s, *(_f32(a) for a in row),
                                   fault=fault)[1], cast), None
    return lax.scan(step, _rounded(_f32(state), cast),
                    (q, k, v, g, beta))[0]


def _head_slices(head):
    n = HEAD_SLICES if head.shape[0] % HEAD_SLICES == 0 else 1
    return head.reshape(n, head.shape[0] // n, head.shape[1])


def logits_at(params, x, sizes: dict, out_positions):
    """The head over rows ``out_positions`` of the residual stream ``x``:
    float32 ``[len(out_positions), V]``, in slices of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        out = jnp.asarray(out_positions, jnp.int32)
        h = _rms(x[out], params["final_norm"], float(sizes["rms_norm_eps"]))
        head = params["lm_head"]
        lg = lax.map(lambda r: h @ _f32(r).T, _head_slices(head))
        return jnp.moveaxis(lg, 0, 1).reshape(len(out), head.shape[0])


def forward(params, tokens, sizes: dict, *, out_positions, **kw):
    """tokens int32 [S] -> (logits float32 [len(out_positions), V], the
    routing margin at those positions, the states at ``state_at`` or None):
    ``hidden`` and ``logits_at``."""
    x, margin, states = hidden(params, tokens, sizes, **kw)
    out = jnp.asarray(out_positions, jnp.int32)
    return logits_at(params, x, sizes, out), margin[out], states


def token_gaps(params, x, sizes: dict, out_positions, next_tokens):
    """Per row of ``out_positions``: the largest logit there less the logit
    of ``next_tokens``' entry (the token the program emitted next), without
    holding ``[rows, V]``: a running maximum over slices of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        out = jnp.asarray(out_positions, jnp.int32)
        h = _rms(x[out], params["final_norm"], float(sizes["rms_norm_eps"]))
        head = params["lm_head"]
        best = lax.map(lambda r: jnp.max(h @ _f32(r).T, axis=-1),
                       _head_slices(head)).max(axis=0)
        picked = jnp.sum(h * _f32(head[jnp.asarray(next_tokens)]), -1)
        return best - picked
