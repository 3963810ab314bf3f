"""The ``falcon_h1`` forward pass (TII Falcon-H1-34B-Instruct) in plain
float32 ``jax.numpy``: the reference the served logits and state pages are
held to.

No kernels, no cache, no batching, no chunked scan: the state-space
recurrence runs TOKEN BY TOKEN (``lax.scan`` over the positions), attention
is full causal softmax, every matrix product at ``highest`` precision.  It
follows the ``falcon_h1`` modeling code of ``transformers`` (``config.json``
names the sizes and the multipliers, not these equations).  With ``u =
RMSNorm(h; g, rms_norm_eps)`` = ``h * rsqrt(mean(h^2) + eps) * g`` and every
multiplier the config's key of that name, for each layer:

    u = RMSNorm(h; input_norm);  h <- h + attn(u) + ssm(u)
    g = RMSNorm(h; pre_ff_norm); h <- h + mlp(g)

- ``attn``: ``q, k, v = (u * attention_in_multiplier) Wq, Wk, Wv``
  (``num_attention_heads`` / ``num_key_value_heads`` heads of ``head_dim``:
  query head h reads K/V head ``h // group``), no biases; ``k = k *
  key_multiplier``; rotary (``rope_theta``, no scaling, pairs ``(i, i +
  D/2)``, all of ``head_dim``) on q and k; causal softmax(``q . k /
  sqrt(D)``) over keys ``j <= i``; ``a = (A Wo) * attention_out_multiplier``;
- ``ssm`` (Mamba-2; nh = ``mamba_n_heads`` heads of P = ``mamba_d_head``,
  N = ``mamba_d_state``, G = ``mamba_n_groups``): ``[z | x | B | C | dt] =
  ((u * ssm_in_multiplier) W_in) * mup_vector`` in that order, widths
  ``d_ssm | d_ssm | G N | G N | nh``, ``mup_vector`` = ``ssm_multipliers[0
  .. 4]`` over those five segments; ``xBC = silu(conv(xBC) + conv_b)``: a
  depthwise causal filter of ``mamba_d_conv`` taps a channel over ``[x | B |
  C]``, ``xBC_{<0} = 0``, the LAST tap on the current token; ``dt =
  softplus(dt + dt_bias)`` (no limits: the family's ``time_step_limit`` is
  (0, inf)); ``A = -exp(A_log)``, a scalar a head;  ``S_t = exp(dt_t A)
  S_{t-1} + dt_t x_t (outer) B_t``;  ``y_t = S_t C_t + D x_t`` (head j reads
  the B and C of group ``j // (nh / G)``); ``y = RMSNorm(y * silu(z);
  ssm_norm)`` over each of G groups of ``d_ssm / G`` channels
  (``mamba_rms_norm``, ``mamba_norm_before_gate`` false); ``m = (y W_out) *
  ssm_out_multiplier``;
- ``mlp``: ``(silu((g W_gate) * mlp_multipliers[0]) * (g W_up)) W_down *
  mlp_multipliers[1]`` of ``intermediate_size`` (``mamba_use_mlp`` read as:
  the block has this feed-forward part; the mixer has no MLP of its own);
- ``h_0 = embed[tokens] * embedding_multiplier``; at the end ``final_norm``
  and the head (untied) times ``lm_head_multiplier``.

A stream's state of a layer at position t is ``S_t [nh, N, P]`` (held here
state-dimension-major, as the program's page is) and the rows ``xBC_{t -
d_conv + 2} .. xBC_t`` of the projection BEFORE the filter.

Departures from the published model: none in a layer (every head, every
state dimension and the whole vocabulary are here); the DEPTH is the
configuration file's ``num_hidden_layers``; no dropout (evaluation).

It reads the parameter tree ``models.falcon_h1.falcon_h1_init`` produces
(weights ``[in, out]``, the head ``[V, H]``, one dict a layer) and upcasts
each tensor where it is used: attention runs in query blocks, the
feed-forward part in row blocks and the head in slices of the vocabulary,
so that 4 layers at 3k positions fit beside the engine.  ``sizes`` is the
configuration file's dict (published keys).

Switches used ONLY for the controls that show the comparison can fail
(``fault``): ``"bf16_state"`` rounds the state to bfloat16 after every token
(what a state pool in the nearest precision below float32 would hold);
``"no_ssm"`` drops the state-space branch (m = 0); ``"d_zero"`` reads D = 0;
``"unit_ssm_multipliers"`` reads ``ssm_multipliers`` as all 1.
``zero_state_at`` = P (a traced scalar; 0 changes nothing) makes rows ``t >=
P`` read a state and filter rows that hold nothing of the rows before P:
what a stream that resumed at P WITHOUT its snapshot would compute.

The state's OWN arithmetic is held apart from everything upstream of it by
``first_layer_steps`` (what layer 0's recurrence consumes at each token,
from the embedding, the norm, ``W_in`` and the filter alone — nothing a
state or an attention computed) and ``carry_state`` (the recurrence over
them from a given state): a page the program held before a run of tokens,
carried by these two, against the page it holds after.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HEAD_SLICES = 16
ROW_BLOCK = 512
FAULTS = ("bf16_state", "no_ssm", "d_zero", "unit_ssm_multipliers")


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, cos, sin):
    """Pairs (i, i + D/2) of the last axis rotated by frequency i."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _f32(a):
    return a.astype(jnp.float32)


def hidden(params, tokens, sizes: dict, *, q_block: int = 128, fault=None,
           zero_state_at=0, state_at=None):
    """tokens int32 [S] -> (the residual stream after the last layer [S,
    H], and where ``state_at`` = t is given every layer's state at position
    t: (S_t ``[layers, nh, N, P]``, filter rows ``[layers, d_conv - 1,
    conv_dim]``), else None)."""
    assert fault is None or fault in FAULTS, fault
    eps = float(sizes["rms_norm_eps"])
    H = int(sizes["hidden_size"])
    nH, nKV = (int(sizes["num_attention_heads"]),
               int(sizes["num_key_value_heads"]))
    D, grp = int(sizes["head_dim"]), nH // nKV
    nh, Pd = int(sizes["mamba_n_heads"]), int(sizes["mamba_d_head"])
    N, G = int(sizes["mamba_d_state"]), int(sizes["mamba_n_groups"])
    taps, d_ssm = int(sizes["mamba_d_conv"]), int(sizes["mamba_d_ssm"])
    assert nh * Pd == d_ssm
    mults = [1.0] * 5 if fault == "unit_ssm_multipliers" \
        else [float(m) for m in sizes["ssm_multipliers"]]
    widths = (d_ssm, d_ssm, G * N, G * N, nh)
    mup = np.concatenate([np.full(w, m, np.float32)
                          for w, m in zip(widths, mults)])
    S = tokens.shape[0]
    inv = float(sizes["rope_theta"]) ** (
        -np.arange(0, D, 2, dtype=np.float64) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    nb = -(-S // q_block)
    pad = nb * q_block - S
    rows = jnp.arange(S)
    cut = jnp.asarray(zero_state_at, jnp.int32)
    at = None if state_at is None else jnp.asarray(state_at, jnp.int32)

    def attention(p, u):
        ua = u * float(sizes["attention_in_multiplier"])
        q = (ua @ _f32(p["wq"])).reshape(S, nH, D)
        k = (ua @ _f32(p["wk"])).reshape(S, nKV, D) \
            * float(sizes["key_multiplier"])
        v = (ua @ _f32(p["wv"])).reshape(S, nKV, D)
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        qf = jnp.pad(q, ((0, pad), (0, 0), (0, 0))) \
            .reshape(nb, q_block, nKV, grp, D)
        cols = jnp.arange(S)[None, :]

        def block(i):
            qi = (i * q_block + jnp.arange(q_block))[:, None]
            s = jnp.einsum("qnmd,tnd->nmqt", qf[i], k) * D ** -0.5
            s = jnp.where((cols <= qi)[None, None], s, -jnp.inf)
            return jnp.einsum("nmqt,tnd->qnmd", jax.nn.softmax(s, -1), v)
        a = lax.map(block, jnp.arange(nb)).reshape(nb * q_block, nH * D)[:S]
        return (a @ _f32(p["wo"])) * float(sizes["attention_out_multiplier"])

    def ssm(p, u):
        proj = ((u * float(sizes["ssm_in_multiplier"])) @ _f32(p["ssm_in"])) \
            * mup
        z, xbc, dt = (proj[:, :d_ssm], proj[:, d_ssm:2 * d_ssm + 2 * G * N],
                      proj[:, 2 * d_ssm + 2 * G * N:])
        padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))   # xBC_t at t + taps-1
        w = _f32(p["conv_w"])                            # [conv_dim, taps]
        mixed = jnp.zeros_like(xbc)
        for j in range(taps):
            src = rows - (taps - 1) + j          # the position tap j reads
            lost = (rows >= cut) & (src < cut)         # (the control only)
            mixed = mixed + jnp.where(lost[:, None], 0.0,
                                      padded[j:j + S] * w[:, j])
        act = jax.nn.silu(mixed + _f32(p["conv_b"]))
        x = act[:, :d_ssm].reshape(S, nh, Pd)
        B = act[:, d_ssm:d_ssm + G * N].reshape(S, G, N)
        C = act[:, d_ssm + G * N:].reshape(S, G, N)
        dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))             # [S, nh]
        decay = jnp.exp(-dt * jnp.exp(_f32(p["A_log"])))
        by_head = lambda a: jnp.repeat(a, nh // G, axis=0)    # noqa: E731

        def token(carry, row):
            state, kept = carry
            t, x_t, B_t, C_t, dt_t, decay_t = row
            state = jnp.where((t == cut) & (cut > 0), 0.0, state)
            state = decay_t[:, None, None] * state \
                + by_head(B_t)[:, :, None] * (dt_t[:, None] * x_t)[:, None, :]
            if fault == "bf16_state":
                # (a convert there and back may be simplified away)
                state = lax.reduce_precision(state, 8, 7)
            y_t = jnp.sum(state * by_head(C_t)[:, :, None], axis=1)
            if at is not None:
                kept = jnp.where(t == at, state, kept)
            return (state, kept), y_t
        zero = jnp.zeros((nh, N, Pd), jnp.float32)
        (_, kept), y = lax.scan(token, (zero, zero),
                                (rows, x, B, C, dt, decay))
        if fault != "d_zero":
            y = y + _f32(p["D"])[:, None] * x
        gated = (y.reshape(S, d_ssm) * jax.nn.silu(z)).reshape(
            S, G, d_ssm // G)
        y = _rms(gated, p["ssm_norm"].reshape(G, d_ssm // G), eps)
        m = (y.reshape(S, d_ssm) @ _f32(p["ssm_out"])) \
            * float(sizes["ssm_out_multiplier"])
        if fault == "no_ssm":
            m = jnp.zeros_like(m)
        if at is None:
            return m, None
        # rows xBC_{t-taps+2} .. xBC_t = padded rows t + 1 .. t + taps - 1
        return m, (kept, lax.dynamic_slice(
            padded, (at + 1, 0), (taps - 1, padded.shape[1])))

    def mlp(p, g):
        m0, m1 = (float(m) for m in sizes["mlp_multipliers"])

        def block(r):
            return ((jax.nn.silu((r @ _f32(p["mlp_gate"])) * m0)
                     * (r @ _f32(p["mlp_up"]))) @ _f32(p["mlp_down"])) * m1
        n = -(-S // ROW_BLOCK)
        gb = jnp.pad(g, ((0, n * ROW_BLOCK - S), (0, 0))) \
            .reshape(n, ROW_BLOCK, H)
        return lax.map(block, gb).reshape(n * ROW_BLOCK, H)[:S]

    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][tokens]) \
            * float(sizes["embedding_multiplier"])
        states = []
        for p in params["layers"][:int(sizes["num_hidden_layers"])]:
            u = _rms(x, p["input_norm"], eps)
            m, state = ssm(p, u)
            states.append(state)
            x = x + attention(p, u) + m
            x = x + mlp(p, _rms(x, p["pre_ff_norm"], eps))
        if at is None:
            return x, None
        return x, (jnp.stack([s for s, _ in states]),
                   jnp.stack([c for _, c in states]))


def first_layer_steps(params, tokens, sizes: dict, *, skip: int = 0,
                      act=None):
    """What LAYER 0's recurrence consumes at each of ``tokens[skip:]``: (x
    ``[T, nh, P]``, B ``[T, G, N]``, dt ``[T, nh]``, decay ``exp(dt A)``
    ``[T, nh]``), float32, ``highest`` precision, from the embedding,
    ``input_norm``, ``W_in`` and the filter.  ``tokens[:skip]`` only feed
    the filter: the ``mamba_d_conv - 1`` tokens before the first wanted one
    (with ``skip`` 0 the first token is position 0 and the filter reads
    zeros before it).

    ``act``: a dtype.  The values are then ROUNDED to it at the four places
    where the configuration says an activation is held in it on the way
    into the state — the embedding after its multiplier, the norm's output,
    the xBC segment of the projection, the filter's output after SiLU; dt
    stays float32 — so that what is left against a served page is the
    state's arithmetic and not those roundings (which alone read 0.4% on a
    page: more than a bfloat16 state adds)."""
    eps = float(sizes["rms_norm_eps"])
    nh, Pd = int(sizes["mamba_n_heads"]), int(sizes["mamba_d_head"])
    N, G = int(sizes["mamba_d_state"]), int(sizes["mamba_n_groups"])
    taps, d_ssm = int(sizes["mamba_d_conv"]), int(sizes["mamba_d_ssm"])
    widths = (d_ssm, d_ssm, G * N, G * N, nh)
    mup = np.concatenate([np.full(w, float(m), np.float32) for w, m
                          in zip(widths, sizes["ssm_multipliers"])])
    kind = None if act is None else jnp.finfo(act)

    def held(a):
        # (``reduce_precision``: a convert there and back may be removed)
        return a if kind is None else lax.reduce_precision(
            a, kind.nexp, kind.nmant)
    p = params["layers"][0]
    S = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        h = held(_f32(params["embed"][tokens])
                 * float(sizes["embedding_multiplier"]))
        u = held(_rms(h, p["input_norm"], eps))
        proj = ((u * float(sizes["ssm_in_multiplier"])) @ _f32(p["ssm_in"])) \
            * mup
        xbc = held(proj[:, d_ssm:2 * d_ssm + 2 * G * N])
        padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
        w = _f32(p["conv_w"])
        mixed = sum(padded[j:j + S] * w[:, j] for j in range(taps))
        act_ = held(jax.nn.silu(mixed + _f32(p["conv_b"])))[skip:]
        dt = jax.nn.softplus(proj[skip:, 2 * d_ssm + 2 * G * N:]
                             + _f32(p["dt_bias"]))
        return (act_[:, :d_ssm].reshape(-1, nh, Pd),
                act_[:, d_ssm:d_ssm + G * N].reshape(-1, G, N), dt,
                jnp.exp(-dt * jnp.exp(_f32(p["A_log"]))))


def carry_state(state, x, B, dt, decay, cast=None):
    """``state [nh, N, P]`` carried over the tokens x ``[T, nh, P]``, B ``[T,
    G, N]``, dt and decay ``[T, nh]`` one at a time in float32; rounded to
    ``cast`` after each (and on entry) when given."""
    nh = x.shape[1]

    def rounded(s):
        # (``reduce_precision``: a convert there and back may be removed by
        # a compiler that keeps excess precision)
        if cast is None:
            return s
        kind = jnp.finfo(cast)
        return lax.reduce_precision(s, kind.nexp, kind.nmant)

    def step(s, row):
        x_t, B_t, dt_t, decay_t = row
        B_h = jnp.repeat(_f32(B_t), nh // B_t.shape[0], axis=0)   # [nh, N]
        return rounded(
            _f32(decay_t)[:, None, None] * s
            + B_h[:, :, None] * (_f32(dt_t)[:, None] * _f32(x_t))[:, None, :]
        ), None
    return lax.scan(step, rounded(_f32(state)), (x, B, dt, decay))[0]


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
        return jnp.moveaxis(lg, 0, 1).reshape(len(out), head.shape[0]) \
            * float(sizes["lm_head_multiplier"])


def forward(params, tokens, sizes: dict, *, out_positions, **kw):
    """tokens int32 [S] -> (logits float32 [len(out_positions), V], the
    states at ``state_at`` or None): ``hidden`` and ``logits_at``."""
    x, states = hidden(params, tokens, sizes, **kw)
    return logits_at(params, x, sizes, out_positions), states


def token_gaps(params, x, sizes: dict, out_positions, next_tokens):
    """Per row of ``out_positions``: the largest logit there less the logit
    of ``next_tokens``' entry (the token the program emitted next), without
    holding ``[rows, V]``: a running maximum over slices of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        out = jnp.asarray(out_positions, jnp.int32)
        h = _rms(x[out], params["final_norm"], float(sizes["rms_norm_eps"]))
        head = params["lm_head"]
        scale = float(sizes["lm_head_multiplier"])
        best = lax.map(lambda r: jnp.max(h @ _f32(r).T, axis=-1),
                       _head_slices(head)).max(axis=0) * scale
        picked = jnp.sum(h * _f32(head[jnp.asarray(next_tokens)]), -1) * scale
        return best - picked
