"""The ``smallthinker`` forward pass (PowerInfer SmallThinker-21BA3B-Instruct)
in plain float32 ``jax.numpy``: the reference the served logits are held to.

No kernels, no cache, no batching, a loop over experts; every matrix
product at ``highest`` precision.  ``config.json`` names the sizes and the
two per-layer lists; the equations are from the ``smallthinker`` modeling
code and the family's paper (written from memory: each line that
``config.json`` does not settle is in the configuration file's
``assumed``).  With ``h`` the residual stream ``[S, H]``, block ``l``:

- ``x = RMSNorm_in(h)`` (``x * rsqrt(mean(x^2) + eps) * w``);
- ROUTE, FROM ``x`` (before the attention): ``logits = x Wr``
  (``moe_num_primary_experts`` of them); the
  ``moe_num_active_primary_experts`` largest logits are chosen; the weights
  are a softmax over THOSE logits (``moe_primary_router_apply_softmax``),
  divided by their sum (``norm_topk_prob``: a no-op but for rounding);
- ``h = h + Attn_l(x)``: ``q = x Wq`` (``num_attention_heads`` of
  ``head_dim``), ``k = x Wk``, ``v = x Wv`` (``num_key_value_heads``: query
  head i reads K/V head ``i // group``), no biases, no q/k norm; where
  ``rope_layout[l] == 1`` rotary (``rope_theta``, no scaling, all
  ``head_dim`` dimensions, pairs ``(i, i + head_dim / 2)``) on q and k,
  where 0 no position encoding; scores ``q . k / sqrt(head_dim)``, causal
  softmax over keys ``s <= t`` and, where ``sliding_window_layout[l] == 1``,
  ``t - s < sliding_window_size`` (the window holds the query's own
  position); ``o = A Wo``, no gate;
- ``z = RMSNorm_post(h)``; ``h = h + sum_j w_j Wdown_e (relu(Wgate_e z) *
  (Wup_e z))`` over the chosen ``e``: the experts read ``z``, their choice
  and weights came from ``x``; no shared expert, no dense layer;

then the final RMS norm and the untied head.

Departures from the published model: none in a layer (every expert, every
head and the whole vocabulary are here); the DEPTH is the configuration
file's (``num_hidden_layers`` layers, the two layouts' first entries); no
dropout (evaluation).

It reads the parameter tree ``models.smallthinker.smallthinker_init``
produces (weights ``[in, out]``, experts ``[E, F, H]``, one dict a layer)
and upcasts each tensor where it is used: attention runs in query blocks,
the experts one at a time and the head in slices of the vocabulary, so that
16k positions fit beside the engine.  ``sizes`` is the configuration file's
dict (published keys).

Switches used ONLY for the controls that show the comparison can fail
(traced flags, one compiled function): ``window`` False attends the whole
context in window layers too; ``rotary_all`` rotates q and k in the full
layers as well; ``router_post`` routes from ``z`` (the post-attention norm:
the usual placement); ``silu`` gates the experts with SiLU; ``softmax_all``
takes the softmax over ALL the logits before the top-k and does not
renormalise.  ``cast`` rounds every matrix product's operands to a narrower
type first: what computing in that precision would give.
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HEAD_SLICES = 16
FLAGS = ("window", "rotary_all", "router_post", "silu", "softmax_all")
TRUE_MODEL = dict(window=True, rotary_all=False, router_post=False,
                  silu=False, softmax_all=False)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, cos, sin):
    """Pairs (i, i + D/2) of the last axis rotated by frequency i."""
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layouts(sizes: dict):
    """(rope_layout, sliding_window_layout) of the layers that are run."""
    L = int(sizes["num_hidden_layers"])
    return (list(sizes["rope_layout"])[:L],
            list(sizes["sliding_window_layout"])[:L])


def route(x, router, sizes: dict, softmax_all=False):
    """x [S, H] fp32 -> (ids [S, k], weights [S, k], margin [S]): the
    margin is how far (in logits) the routing is from another outcome: the
    k-th largest logit less the (k+1)-th."""
    k = int(sizes["moe_num_active_primary_experts"])
    logits = x @ router.astype(jnp.float32)
    top, ids = lax.top_k(logits, k + 1)
    w = jax.nn.softmax(top[:, :k], axis=-1)
    if sizes.get("norm_topk_prob", True):
        w = w / w.sum(-1, keepdims=True)
    # the control: a softmax over every logit, the chosen ones' shares kept
    # as they are (they sum to less than 1)
    w_all = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                ids[:, :k], axis=1)
    return ids[:, :k], jnp.where(softmax_all, w_all, w), \
        top[:, k - 1] - top[:, k]


def forward(params, tokens, sizes: dict, *, out_positions, q_block: int = 128,
            cast=None, window=True, rotary_all=False, router_post=False,
            silu=False, softmax_all=False):
    """tokens int32 [S] -> (logits float32 [len(out_positions), V], routing
    margin [len(out_positions)]: the least over the layers at that
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
        W = int(sizes["sliding_window_size"])
        E = int(sizes["moe_num_primary_experts"])
        S = tokens.shape[0]
        inv = float(sizes["rope_theta"]) ** (
            -np.arange(0, D, 2, dtype=np.float64) / D)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
            * jnp.asarray(inv, jnp.float32)[None]
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        nb = -(-S // q_block)
        pad = nb * q_block - S
        window, rotary_all, router_post, silu, softmax_all = (
            jnp.asarray(f, bool) for f in (window, rotary_all, router_post,
                                           silu, softmax_all))

        def attention(p, x, rotary: bool, sliding: bool):
            q = mm(x, p["wq"]).reshape(S, nH, D)
            k = mm(x, p["wk"]).reshape(S, nKV, D)
            v = mm(x, p["wv"]).reshape(S, nKV, D)
            rotate = jnp.logical_or(rotary, rotary_all)
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
            return mm(a, p["wo"])

        def experts(p, z, ids, w):
            def expert(e, y):
                we = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
                # [F, H] as held: gate/up contract H, down maps F -> H
                g = f32(z) @ f32(p["w_gate"][e]).T
                u = f32(z) @ f32(p["w_up"][e]).T
                g = jnp.where(silu, jax.nn.silu(g), jnp.maximum(g, 0.0))
                return y + we[:, None] * (f32(g * u) @ f32(p["w_down"][e]))
            return lax.fori_loop(0, E, expert, jnp.zeros_like(z))

        h = params["embed"][tokens].astype(jnp.float32)
        margins = []
        for p, rotary, sliding in zip(params["layers"], *layouts(sizes)):
            x = _rms(h, p["input_norm"], eps)
            h = h + attention(p, x, bool(rotary), bool(sliding))
            z = _rms(h, p["post_attn_norm"], eps)
            ids, w, margin = route(jnp.where(router_post, z, x), p["router"],
                                   sizes, softmax_all)
            margins.append(margin)
            h = h + experts(p, z, ids, w)
        out = jnp.asarray(out_positions, jnp.int32)
        hn = _rms(h[out], params["final_norm"], eps)
        head = params["lm_head"]
        n = HEAD_SLICES if head.shape[0] % HEAD_SLICES == 0 else 1
        logits = lax.map(lambda rows: mm(hn, rows.T),
                         head.reshape(n, head.shape[0] // n, H))
        logits = jnp.moveaxis(logits, 0, 1).reshape(len(out), head.shape[0])
        return logits, jnp.min(jnp.stack(margins), axis=0)[out]
