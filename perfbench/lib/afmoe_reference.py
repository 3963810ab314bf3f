"""The ``afmoe`` forward pass (Arcee Trinity Mini / Nano) in plain float32
``jax.numpy``: the reference the served logits are held to.

No kernels, no cache, no batching, a loop over experts; every matrix
product at ``highest`` precision.  It follows the ``afmoe`` modeling code of
``transformers`` (``config.json`` names the sizes, not these equations):

- the embedded row times ``sqrt(hidden_size)`` (``mup_enabled``);
- every layer, sandwich norms: ``h = h + N2(Attn(N1(h)))``; ``h = h +
  N4(FFN(N3(h)))``, RMS norms ``x * rsqrt(mean(x^2) + eps) * w``;
- attention: ``q = x Wq`` (``num_attention_heads`` of ``head_dim``), ``k = x
  Wk``, ``v = x Wv`` (``num_key_value_heads``: query head h reads K/V head
  ``h // group``), no biases; RMS norm over ``head_dim`` on q and on k;
  rotary (``rope_theta``, no scaling, pairs ``(i, i + head_dim / 2)``) on q
  and k in ``sliding_attention`` layers ONLY, ``full_attention`` layers
  carry no position encoding; scores ``q . k / sqrt(head_dim)``, causal
  softmax over keys ``j <= i`` and, in a sliding layer, ``i - j <
  sliding_window``; ``o = (A * sigmoid(x Wg)) Wo``;
- layers ``0 .. num_dense_layers - 1``: ``down(silu(gate x) * up x)`` of
  ``intermediate_size``;
- every other layer: ``s = sigmoid(x Wr)``; the ``num_experts_per_tok``
  largest ``s + b`` are chosen (one group: no group limit); weights ``s`` at
  the chosen / their sum (+1e-20, ``route_norm``) x ``route_scale``; each
  expert a gated SiLU FFN of ``moe_intermediate_size``; plus the shared
  expert for every token;
- final RMS norm, untied head.

Departures from the published model: none in a layer (every expert, every
head and the whole vocabulary are here); the DEPTH is the configuration
file's (``num_hidden_layers`` layers, the first ``num_dense_layers`` dense,
``layer_types`` its first entries); no dropout (evaluation).

It reads the parameter tree ``models.afmoe.afmoe_init`` produces (weights
``[in, out]``, routed experts ``[E, F, H]``, one dict a layer) and upcasts
each tensor where it is used: attention runs in query blocks, the experts
one at a time and the head in slices of the vocabulary, so that 33k
positions fit beside the engine.  ``sizes`` is the configuration file's
dict (published keys).

Switches used ONLY for the controls that show the comparison can fail
(traced flags, one compiled function): ``window`` False attends the whole
context in sliding layers too; ``rotary_all`` rotates q and k in full
layers as well; ``gate`` False leaves the output gate out.  ``cast`` rounds
every matrix product's operands to a narrower type first: what computing in
that precision would give.
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


def layer_types(sizes: dict) -> list:
    return list(sizes["layer_types"])[:int(sizes["num_hidden_layers"])]


def route(x, router, bias, sizes: dict):
    """x [S, H] fp32 -> (ids [S, k], weights [S, k], margin [S]): the
    margin is how far (in ``c = s + b``) the routing is from another
    outcome: the k-th largest ``c`` less the (k+1)-th."""
    k = int(sizes["num_experts_per_tok"])
    s = jax.nn.sigmoid(x @ router.astype(jnp.float32))
    top, ids = lax.top_k(s + bias.astype(jnp.float32), k + 1)
    w = jnp.take_along_axis(s, ids[:, :k], axis=1)
    if sizes.get("route_norm", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return ids[:, :k], w * float(sizes["route_scale"]), \
        top[:, k - 1] - top[:, k]


def forward(params, tokens, sizes: dict, *, out_positions, q_block: int = 128,
            cast=None, window=True, rotary_all=False, gate=True):
    """tokens int32 [S] -> (logits float32 [len(out_positions), V], routing
    margin [len(out_positions)]: the least over the expert layers at that
    position)."""
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
        W = int(sizes["sliding_window"])
        E = int(sizes["num_experts"])
        S = tokens.shape[0]
        inv = float(sizes["rope_theta"]) ** (
            -np.arange(0, D, 2, dtype=np.float64) / D)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
            * jnp.asarray(inv, jnp.float32)[None]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        nb = -(-S // q_block)
        pad = nb * q_block - S
        window, rotary_all, gate = (jnp.asarray(f, bool)
                                    for f in (window, rotary_all, gate))

        def attention(p, x, sliding: bool):
            h = _rms(x, p["input_norm"], eps)
            q = _rms(mm(h, p["wq"]).reshape(S, nH, D), p["q_norm"], eps)
            k = _rms(mm(h, p["wk"]).reshape(S, nKV, D), p["k_norm"], eps)
            v = mm(h, p["wv"]).reshape(S, nKV, D)
            g = mm(h, p["wg"])
            rotate = jnp.logical_or(sliding, rotary_all)
            q = jnp.where(rotate, _rope(q, cos, sin), q)
            k = jnp.where(rotate, _rope(k, cos, sin), k)
            qf = jnp.pad(f32(q), ((0, pad), (0, 0), (0, 0))) \
                .reshape(nb, q_block, nKV, grp, D)
            kf, vf = f32(k), f32(v)
            cols = jnp.arange(S)[None, :]

            def block(i):
                rows = (i * q_block + jnp.arange(q_block))[:, None]
                ok = cols <= rows
                if sliding:
                    ok = ok & ((rows - cols < W) | ~window)
                s = jnp.einsum("qnmd,tnd->nmqt", qf[i], kf) * D ** -0.5
                s = jnp.where(ok[None, None], s, -jnp.inf)
                return jnp.einsum("nmqt,tnd->qnmd", f32(jax.nn.softmax(s, -1)),
                                  vf)
            a = lax.map(block, jnp.arange(nb)).reshape(nb * q_block,
                                                       nH * D)[:S]
            a = jnp.where(gate, a * jax.nn.sigmoid(g), a)
            return x + _rms(mm(a, p["wo"]), p["post_attn_norm"], eps)

        def ffn(x, gate_w, up, down):
            return mm(jax.nn.silu(mm(x, gate_w)) * mm(x, up), down)

        def experts(p, h):
            ids, w, margin = route(h, p["router"], p["router_bias"], sizes)

            def expert(e, y):
                we = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
                # [F, H] as held: gate/up contract H, down maps F -> H
                g = f32(h) @ f32(p["w_gate"][e]).T
                u = f32(h) @ f32(p["w_up"][e]).T
                return y + we[:, None] * (f32(jax.nn.silu(g) * u)
                                          @ f32(p["w_down"][e]))
            y = lax.fori_loop(0, E, expert, jnp.zeros_like(h))
            return y + ffn(h, p["shared_gate"], p["shared_up"],
                           p["shared_down"]), margin

        x = params["embed"][tokens].astype(jnp.float32)
        if sizes.get("mup_enabled", True):
            x = x * H ** 0.5
        margins = []
        for i, (p, kind) in enumerate(zip(params["layers"],
                                          layer_types(sizes))):
            x = attention(p, x, kind == "sliding_attention")
            h = _rms(x, p["pre_mlp_norm"], eps)
            if i < int(sizes["num_dense_layers"]):
                y = ffn(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
            else:
                y, margin = experts(p, h)
                margins.append(margin)
            x = x + _rms(y, p["post_mlp_norm"], eps)
        out = jnp.asarray(out_positions, jnp.int32)
        h = _rms(x[out], params["final_norm"], eps)
        head = params["lm_head"]
        n = HEAD_SLICES if head.shape[0] % HEAD_SLICES == 0 else 1
        logits = lax.map(lambda rows: mm(h, rows.T),
                         head.reshape(n, head.shape[0] // n, H))
        logits = jnp.moveaxis(logits, 0, 1).reshape(len(out), head.shape[0])
        margin = jnp.min(jnp.stack(margins), axis=0)[out] if margins \
            else jnp.full((len(out),), jnp.inf)
        return logits, margin
