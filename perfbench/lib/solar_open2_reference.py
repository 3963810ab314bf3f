"""The ``solar_open2`` forward pass (upstage Solar-Open2-250B) in plain float32
``jax.numpy``: the reference the served logits, state pages and K/V pages are
held to.

No kernels, no cache, no batching, no chunked form: the delta rule runs TOKEN
BY TOKEN (``lax.scan`` over the positions), grouped-query attention is a full
causal softmax in query blocks, a loop over experts, every matrix product at
``highest`` precision.  Written from the keys of the published ``config.json``
and the family's papers, from memory (no network here); what the keys do not
settle is listed under ``assumed`` in the configuration file.  ``RMS(h; w) =
h rsqrt(mean(h^2) + eps) w``.  Layer ``l`` (0-based):

    a = h + Mixer_l(RMS(h; input_norm));   h' = a + MoE(RMS(a; post_norm))

- ``Mixer_l`` for ``l`` NOT in ``gqa_layers`` — Kimi Delta Attention, nh =
  ``linear_attn_config.num_heads`` heads of d = ``.head_dim``: ``[q~ | k~ |
  v~] = x W_qkv``; each through its own depthwise causal filter of
  ``short_conv_kernel_size`` taps (zeros before position 0, the LAST tap on
  the current token, no bias), then SiLU; ``q = q / |q|_head * d^-0.5``, ``k
  = k / |k|_head`` (``|.|`` = sqrt(sum of squares + 1e-6)); ``g =
  -exp(A_log_h) softplus((x W_f_down) W_f_up + dt_bias)`` a channel; **``beta
  = 2 sigmoid(x W_beta)``** a head (``kda_allow_neg_eigval``); ``S' =
  Diag(e^g) S_{t-1}``, ``S_t = S' + beta k (v - S'^T k)^T``, ``o = S_t^T q``;
  ``y = RMS_head(o; o_norm) . sigmoid((x W_g_down) W_g_up)``; ``y W_o``;
- ``Mixer_l`` for ``l`` in ``gqa_layers`` — grouped-query attention: ``q = x
  W_q`` (``num_attention_heads`` of ``head_dim``), ``k = x W_k``, ``v = x
  W_v`` (``num_key_value_heads``: query head h reads K/V head ``h //
  group``), no bias, no q/k norm, NO rotation (``use_rope: false``); scores
  ``q . k / sqrt(head_dim)``; causal softmax; ``(A . sigmoid(x W_g)) W_o``
  (``use_gqa_gate``);
- ``MoE``: ``s = sigmoid(x W_r)`` over ``n_routed_experts``; the
  ``num_experts_per_tok`` largest ``s + b`` are chosen (b for choosing
  only); weights ``s`` at the chosen / (their sum + 1e-20)
  (``norm_topk_prob``) x ``routed_scaling_factor``; each expert ``W2
  (silu(W1 x) * W3 x)`` of ``moe_intermediate_size``; plus the shared expert
  on every token;
- ``h_0 = embed[tokens]``; at the end ``RMS(.; final_norm)`` and the untied
  head.

A stream's state of a KDA layer at position t is ``S_t [nh, d_k, d_v]`` and
the rows ``t - taps + 2 .. t`` of ``[q~ | k~ | v~]`` BEFORE the filter; of a
grouped-query layer, the rows ``k_j, v_j`` for ``j <= t`` (``kv_rows`` gives
layer 0's, which are functions of the embeddings alone).

Departures from the published model, each forced by the cut the configuration
file states: the DEPTH is ``num_hidden_layers`` of the file (``gqa_layers`` is
read under it); it is given the same SHARE ``held = (first, count)`` of the
routed experts as the program (routing is over all ``n_routed_experts``, the
experts outside the share add nothing); the vocabulary is the rows the
parameter tree holds; no dropout (evaluation).  None in the mathematics.

It reads the parameter tree ``models.solar_open2.solar_open2_init`` produces
(weights ``[in, out]``, routed experts ``[E_held, F, H]``, one dict a layer)
and upcasts each tensor where it is used; every per-row product runs in row
blocks, attention in query blocks and the KDA layers in blocks of
``KDA_BLOCK`` rows, so that a 33k-token session fits beside the weights.
``sizes`` is the configuration file's dict.

Switches used ONLY for the controls that show the comparison can fail
(``fault``, by name or as ``fault_code``'s number, which may be TRACED: one
compiled program then reads every wrong model and, at 0, the true one):
``"no_two"`` reads ``beta = sigmoid(.)`` (a write strength under 1: the
published KDA without negative eigenvalues); ``"no_gate"`` drops the
attention's output gate; ``"bf16_state"`` rounds the state to bfloat16 after
every token.  ``zero_state_at`` = P (a traced scalar; 0 changes nothing)
makes rows ``t >= P`` read a state and filter rows that hold nothing of the
rows before P: what a stream that resumed at P WITHOUT its snapshot would
compute.  ``cast`` (static) rounds every matrix product's operands — weights
and the activations on their way in — to a narrower type: what computing in
that precision would give.
"""
import jax
import jax.numpy as jnp
from jax import lax

ROW_BLOCK = 512
KDA_BLOCK = 2048
HEAD_SLICES = 8
L2_EPS = 1e-6
FAULTS = ("no_two", "no_gate", "bf16_state")


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


def _rounded(a, dtype):
    """``a`` held in ``dtype`` (``reduce_precision``: a convert there and
    back may be removed by a compiler that keeps excess precision)."""
    if dtype is None:
        return a
    kind = jnp.finfo(dtype)
    a = jnp.clip(a, float(kind.min), float(kind.max))
    return lax.reduce_precision(a, kind.nexp, kind.nmant)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


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


def layer_kinds(sizes: dict):
    """"gqa" / "kda" of layers 0 .. ``num_hidden_layers`` - 1."""
    full = set(sizes["gqa_layers"])
    return ["gqa" if l in full else "kda"
            for l in range(int(sizes["num_hidden_layers"]))]


def _kda_dims(sizes):
    lin = sizes["linear_attn_config"]
    return (int(lin["num_heads"]), int(lin["head_dim"]),
            int(lin["short_conv_kernel_size"]))


def _unit(a):
    return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)


def delta_step(S, q, k, v, g, beta, fault=None):
    """One token of one stream: S ``[nh, dk, dv]``, q / k / g ``[nh, dk]``, v
    ``[nh, dv]``, beta ``[nh]`` in (0, 2) -> (o ``[nh, dv]``, S_t)."""
    S = jnp.exp(g)[..., None] * S
    r = jnp.einsum("hkv,hk->hv", S, k)
    S = S + k[..., None] * (beta[:, None] * (v - r))[:, None, :]
    S = _switched(fault, "bf16_state", _rounded(S, jnp.bfloat16), S)
    return jnp.einsum("hkv,hk->hv", S, q), S


def carry_state(state, q, k, v, g, beta, cast=None):
    """``state [nh, dk, dv]`` carried over the tokens q / k / g ``[T, nh,
    dk]``, v ``[T, nh, dv]``, beta ``[T, nh]`` one at a time in float32;
    rounded to ``cast`` after each (and on entry) when given."""
    def step(s, row):
        return _rounded(delta_step(s, *(a.astype(jnp.float32)
                                        for a in row))[1], cast), None
    return lax.scan(step, _rounded(state.astype(jnp.float32), cast),
                    (q, k, v, g, beta))[0]


def hidden(params, tokens, sizes: dict, *, q_block: int = 128, fault=None,
           zero_state_at=0, state_at=None, cast=None):
    """tokens int32 [S] -> (the residual stream after the last layer [S, H],
    the least routing margin a position over the expert layers [S], and
    where ``state_at`` = t is given every KDA layer's state at position t:
    (S_t ``[kda layers, nh, dk, dv]``, filter rows ``[kda layers, taps - 1, 3
    nh d]``), else None)."""
    eps = float(sizes["rms_norm_eps"])
    nh, d, taps = _kda_dims(sizes)
    nH, nKV, D = (int(sizes["num_attention_heads"]),
                  int(sizes["num_key_value_heads"]), int(sizes["head_dim"]))
    S = tokens.shape[0]
    rows = jnp.arange(S)
    cut = jnp.asarray(zero_state_at, jnp.int32)
    at = None if state_at is None else jnp.asarray(state_at, jnp.int32)
    nb = -(-S // q_block)
    pad = nb * q_block - S

    def W(a):                     # a weight as the precision under test holds it
        return _rounded(a.astype(jnp.float32), cast)

    def A(a):                     # ... and an activation on its way in
        return _rounded(a, cast)

    def kda_steps(p, u, before, start):
        """What the recurrence consumes at each row of normed ``u [B, H]``
        (positions ``start ..``): q, k, v, g, beta and the projected rows
        with ``before`` (the ``taps - 1`` rows ahead) in front."""
        B = u.shape[0]
        qkv = _rows(lambda r: A(r) @ W(p["w_qkv"]), u)
        padded = jnp.concatenate([before, qkv])          # row t at t + taps-1
        w = p["conv_w"].astype(jnp.float32)              # [3 nh d, taps]
        pos = start + jnp.arange(B)
        mixed = jnp.zeros_like(qkv)
        for j in range(taps):
            term = A(padded[j:j + B]) * w[:, j]
            src = pos - (taps - 1) + j           # the position tap j reads
            term = jnp.where(((pos >= cut) & (src < cut) & (cut > 0))[:, None],
                             0.0, term)
            mixed = mixed + term
        act = jax.nn.silu(mixed).reshape(B, 3, nh, d)
        q, k, v = act[:, 0], act[:, 1], act[:, 2]
        f = A(A(u) @ W(p["w_f_down"])) @ W(p["w_f_up"]) \
            + p["dt_bias"].astype(jnp.float32)
        g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] \
            * jax.nn.softplus(f.reshape(B, nh, d))
        s = jax.nn.sigmoid(A(u) @ W(p["w_beta"]))
        beta = _switched(fault, "no_two", s, 2.0 * s)
        return _unit(q) * d ** -0.5, _unit(k), v, g, beta, padded

    def kda(p, u):
        """Blocks of ``KDA_BLOCK`` rows at a time (the projected rows of 33k
        tokens are 3.2 GB in float32), the state and the filters' last rows
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
            q, k, v, g, beta, padded = kda_steps(p, u_b, tail, i * B)
            (state, kept), o = lax.scan(
                token, (state, kept),
                (i * B + jnp.arange(B), q, k, v, g, beta))
            if at is not None:
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
        gate = jax.nn.sigmoid(_rows(
            lambda r: A(A(r) @ W(p["w_g_down"])) @ W(p["w_g_up"]), u))
        y = _rms(o, p["o_norm"], eps).reshape(S, nh * d) * gate
        m = _rows(lambda r: A(r) @ W(p["wo"]), y)
        return m, None if at is None else (kept, kept_rows)

    def gqa(p, u):
        q = _rows(lambda r: A(r) @ W(p["wq"]), u).reshape(S, nKV, nH // nKV,
                                                          D)
        k = A(_rows(lambda r: A(r) @ W(p["wk"]), u).reshape(S, nKV, D))
        v = A(_rows(lambda r: A(r) @ W(p["wv"]), u).reshape(S, nKV, D))
        qf = jnp.pad(A(q), ((0, pad), (0, 0), (0, 0), (0, 0)))

        def block(i):
            qb = lax.dynamic_slice_in_dim(qf, i * q_block, q_block, 0)
            s = jnp.einsum("qnmd,tnd->nmqt", qb, k) * D ** -0.5
            qi = i * q_block + jnp.arange(q_block)
            s = jnp.where(rows[None, None, None, :] <= qi[None, None, :, None],
                          s, -jnp.inf)
            return jnp.einsum("nmqt,tnd->qnmd", A(jax.nn.softmax(s, -1)), v)
        o = lax.map(block, jnp.arange(nb)).reshape(nb * q_block, nH * D)[:S]
        gate = jax.nn.sigmoid(_rows(lambda r: A(r) @ W(p["wg"]), u))
        o = _switched(fault, "no_gate", o, o * gate)
        return _rows(lambda r: A(r) @ W(p["wo"]), o)

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        states, margins = [], []
        for kind, p in zip(layer_kinds(sizes), params["layers"]):
            u = _rms(x, p["input_norm"], eps)
            if kind == "kda":
                m, state = kda(p, u)
                states.append(state)
            else:
                m = gqa(p, u)
            x = x + m
            y, margin = expert_layer(p, _rms(x, p["post_norm"], eps), sizes,
                                     cast)
            x = x + y
            margins.append(margin)
        margin = jnp.stack(margins).min(axis=0)
        if at is None:
            return x, margin, None
        return x, margin, (jnp.stack([s for s, _ in states]),
                           jnp.stack([c for _, c in states]))


def expert_layer(p, h, sizes: dict, cast=None):
    """The expert layer of normed ``h [S, H]`` with the share ``sizes["held"]``
    of the routed experts: (what the held experts and the shared expert add,
    the routing margin a row).  Routing stays in float32 whatever ``cast``."""
    def W(a):
        return _rounded(a.astype(jnp.float32), cast)

    def A(a):
        return _rounded(a, cast)

    def ffn(h, gate, up, down):
        return _rows(lambda r: A(jax.nn.silu(A(r) @ W(gate))
                               * (A(r) @ W(up))) @ W(down), h)
    ids, w, margin = route(h, p["router"], p["router_bias"], sizes)
    first, count = sizes["held"]

    def expert(e, y):
        we = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)

        def rows_of(r):          # [F, H] as held: gate / up contract H
            return A(jax.nn.silu(A(r) @ W(p["w_gate"][e]).T)
                     * (A(r) @ W(p["w_up"][e]).T)) @ W(p["w_down"][e])
        return y + we[:, None] * _rows(rows_of, h)
    y = lax.fori_loop(0, count, expert, jnp.zeros_like(h))
    return y + ffn(h, p["shared_gate"], p["shared_up"],
                   p["shared_down"]), margin


def route(x, router, bias, sizes: dict):
    """x [S, H] fp32 -> (ids [S, k], weights [S, k], margin [S]): the margin
    is how far (in ``s + b``) the routing is from another outcome that would
    change what the HELD experts add: the least distance of a held expert's
    score from the top-k boundary."""
    k = int(sizes["num_experts_per_tok"])
    first, count = sizes["held"]
    s = jax.nn.sigmoid(x @ router.astype(jnp.float32))
    c = s + bias.astype(jnp.float32)
    top, ids = lax.top_k(c, k + 1)
    w = jnp.take_along_axis(s, ids[:, :k], axis=1)
    if sizes.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * float(sizes["routed_scaling_factor"])
    held = c[:, first:first + count]
    chosen = held >= top[:, k - 1:k]
    dist = jnp.where(chosen, held - top[:, k:k + 1], top[:, k - 1:k] - held)
    return ids[:, :k], w, dist.min(axis=-1)


def kv_rows(params, tokens, sizes: dict):
    """LAYER 0's K and V rows of ``tokens [T]`` (a grouped-query layer: its
    rows are functions of the embeddings alone): two ``[T, nKV, D]``."""
    assert layer_kinds(sizes)[0] == "gqa", sizes["gqa_layers"]
    nKV, D = int(sizes["num_key_value_heads"]), int(sizes["head_dim"])
    p = params["layers"][0]
    with jax.default_matmul_precision("highest"):
        u = _rms(params["embed"][tokens].astype(jnp.float32),
                 p["input_norm"], float(sizes["rms_norm_eps"]))
        return tuple((u @ p[w].astype(jnp.float32)).reshape(-1, nKV, D)
                     for w in ("wk", "wv"))


def _head_slices(head):
    n = HEAD_SLICES if head.shape[0] % HEAD_SLICES == 0 else 1
    return head.reshape(n, head.shape[0] // n, head.shape[1])


def logits_at(params, x, sizes: dict, out_positions, cast=None):
    """The head over rows ``out_positions`` of the residual stream ``x``:
    float32 ``[len(out_positions), V]``, in slices of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        out = jnp.asarray(out_positions, jnp.int32)
        h = _rounded(_rms(x[out], params["final_norm"],
                          float(sizes["rms_norm_eps"])), cast)
        head = params["lm_head"]
        lg = lax.map(lambda r: h @ _rounded(r.astype(jnp.float32), cast).T,
                     _head_slices(head))
        return jnp.moveaxis(lg, 0, 1).reshape(len(out), head.shape[0])


def forward(params, tokens, sizes: dict, *, out_positions, cast=None, **kw):
    """tokens int32 [S] -> (logits float32 [len(out_positions), V], the
    routing margin at those positions, the states at ``state_at`` or None):
    ``hidden`` and ``logits_at``."""
    x, margin, states = hidden(params, tokens, sizes, cast=cast, **kw)
    out = jnp.asarray(out_positions, jnp.int32)
    return logits_at(params, x, sizes, out, cast), margin[out], states


def token_gaps(params, x, sizes: dict, out_positions, next_tokens):
    """Per row of ``out_positions``: the largest logit there less the logit
    of ``next_tokens``' entry (the token the program emitted next), without
    holding ``[rows, V]``: a running maximum over slices of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        out = jnp.asarray(out_positions, jnp.int32)
        h = _rms(x[out], params["final_norm"], float(sizes["rms_norm_eps"]))
        head = params["lm_head"]
        best = lax.map(lambda r: jnp.max(h @ r.astype(jnp.float32).T,
                                         axis=-1),
                       _head_slices(head)).max(axis=0)
        picked = jnp.sum(h * head[jnp.asarray(next_tokens)]
                         .astype(jnp.float32), -1)
        return best - picked
