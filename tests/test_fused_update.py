"""Fused Pallas multi-tensor optimizer apply (ops/fused_update.py) vs the
optax reference apply — the parity contract for the reference's
``csrc/adam/multi_tensor_adam.cu`` equivalent.

Parity tiers:
- moments: BIT-equal with optax (same association order, f32 throughout);
- params (deterministic path): equal to within ~2 f32 ulp — strict bitwise
  equality across two separately-compiled XLA programs is not achievable
  because XLA contracts ``p + u*lr`` into an FMA inside one fusion and not
  the other (verified: one jit of ``p + u*lr`` vs staged mul/add differs in
  the last ulp on CPU); the FMA result is the *more* accurate one;
- params (stochastic-rounding path, seeded): both engines land within one
  bf16 ulp of the same f32 trajectory, so trajectories agree to bf16
  tolerance.

Engine tier runs on the 8-device CPU mesh under ZeRO-2, covering the
fp32-master, master-free bf16+SR, and gas>1 scan paths, plus the
``optimizer.params.fused`` config knob in both positions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deepspeed_tpu.ops import fused_update
from deepspeed_tpu.ops.fused_update import (fused_adam, FusedAdamState,
                                            leaf_moment_views)
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.parallel.topology import build_mesh

B1, B2, EPS, WD = 0.9, 0.999, 1e-8, 0.01


def _tree(seed=0, dtype=np.float32):
    """Off-tile leaves (always packed) plus two the plan can update in
    place — a 2-D matrix and a stacked 3-D one — once ``layout`` lowers
    the size threshold to them."""
    r = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(r.standard_normal((37, 5)).astype(dtype)),
        "big": jnp.asarray(r.standard_normal(140001).astype(dtype)),
        "b": jnp.asarray(r.standard_normal(()).astype(dtype)),
        "mat": jnp.asarray(r.standard_normal((64, 256)).astype(dtype)),
        "stack": jnp.asarray(r.standard_normal((3, 32, 128)).astype(dtype)),
    }


@pytest.fixture(params=["packed", "inplace"])
def layout(request, monkeypatch):
    """Every transform-level test runs twice: all leaves through the
    packed group buffer (the default threshold is far above this tree),
    and with ``mat`` and ``stack`` updated where they lie."""
    if request.param == "inplace":
        monkeypatch.setattr(fused_update, "_INPLACE_MIN_ELEMS", 1 << 12)
    n_inplace = len(fused_update.update_plan(
        jax.tree_util.tree_leaves(_tree())).inplace)
    assert n_inplace == (2 if request.param == "inplace" else 0)
    return request.param


def _grads(i, like):
    r = np.random.default_rng(1000 + i)
    return jax.tree_util.tree_map(
        lambda x: jnp.asarray(
            r.standard_normal(x.shape).astype(np.float32)).astype(x.dtype),
        like)


def _sched(c):
    return jnp.asarray(1e-3, jnp.float32)


def _assert_moments_bitexact(ref_state, fs, params, step=0):
    """optax mu/nu vs the fused V-interleaved buffers, per leaf via
    leaf_moment_views (the buffer layout interleaves every leaf over
    virtual-shard rows, so raw prefix slices are meaningless)."""
    mv, vv = leaf_moment_views(fs, params)
    for k in params:
        np.testing.assert_array_equal(
            np.asarray(ref_state.mu[k]), np.asarray(mv[k]),
            err_msg=f"first moment diverged at step {step} leaf {k}")
        np.testing.assert_array_equal(
            np.asarray(ref_state.nu[k]), np.asarray(vv[k]),
            err_msg=f"second moment diverged at step {step} leaf {k}")


@pytest.mark.usefixtures("layout")
class TestTransformParity:
    def test_adamw_moments_bitexact_params_ulp(self):
        params = _tree()
        ref = optax.adamw(_sched, b1=B1, b2=B2, eps=EPS, weight_decay=WD)
        fus = fused_adam(_sched, B1, B2, EPS, WD, adam_w_mode=True)
        rs, fs = ref.init(params), fus.init(params)
        p_ref = p_fus = params
        upd_ref = jax.jit(ref.update)
        upd_fus = jax.jit(fus.fused_apply)
        n = sum(int(l.size) for l in jax.tree_util.tree_leaves(params))
        for i in range(4):
            g = _grads(i, params)
            u, rs = upd_ref(g, rs, p_ref)
            p_ref = optax.apply_updates(p_ref, u)
            p_fus, fs = upd_fus(g, fs, p_fus)
            _assert_moments_bitexact(rs[0], fs, params, step=i)
            for k in params:
                np.testing.assert_allclose(
                    np.asarray(p_ref[k]), np.asarray(p_fus[k]),
                    rtol=1e-6, atol=1e-7, err_msg=f"step {i} leaf {k}")
        # the pad regions of the fused buffers stay exactly zero: the
        # buffer can hold at most n nonzero (real-element) entries
        assert np.count_nonzero(np.asarray(fs.m[0])) <= n

    def test_coupled_adam_parity(self):
        """adam_w_mode=False folds decay into the grad BEFORE the moments
        (the engine's classic-Adam chain)."""
        params = _tree(3)
        ref = optax.chain(optax.add_decayed_weights(WD),
                          optax.scale_by_adam(b1=B1, b2=B2, eps=EPS),
                          optax.scale_by_learning_rate(_sched))
        fus = fused_adam(_sched, B1, B2, EPS, WD, adam_w_mode=False)
        rs, fs = ref.init(params), fus.init(params)
        p_ref = p_fus = params
        for i in range(3):
            g = _grads(i, params)
            u, rs = jax.jit(ref.update)(g, rs, p_ref)
            p_ref = optax.apply_updates(p_ref, u)
            p_fus, fs = jax.jit(fus.fused_apply)(g, fs, p_fus)
        for k in params:
            np.testing.assert_allclose(np.asarray(p_ref[k]),
                                       np.asarray(p_fus[k]),
                                       rtol=1e-6, atol=1e-7)

    def test_clip_coeff_folded_in_kernel(self):
        """fused_apply(clip_coeff=c) == fused_apply on pre-scaled grads."""
        params = _tree(4)
        fus = fused_adam(_sched, B1, B2, EPS, WD)
        fs = fus.init(params)
        g = _grads(0, params)
        c = jnp.asarray(0.37, jnp.float32)
        p_a, _ = jax.jit(fus.fused_apply)(
            jax.tree_util.tree_map(lambda x: x * c, g), fs, params)
        p_b, _ = jax.jit(lambda g, s, p: fus.fused_apply(
            g, s, p, clip_coeff=c))(g, fs, params)
        for k in params:
            np.testing.assert_allclose(np.asarray(p_a[k]),
                                       np.asarray(p_b[k]),
                                       rtol=1e-6, atol=1e-7)

    def test_optax_update_contract(self):
        """The generic optax-style update (delta + apply_updates) lands on
        the fused_apply params (generic callers keep working)."""
        params = _tree(5)
        fus = fused_adam(_sched, B1, B2, EPS, WD)
        fs = fus.init(params)
        g = _grads(0, params)
        u, _ = jax.jit(fus.update)(g, fs, params)
        via_update = optax.apply_updates(params, u)
        direct, _ = jax.jit(fus.fused_apply)(g, fs, params)
        for k in params:
            np.testing.assert_allclose(np.asarray(via_update[k]),
                                       np.asarray(direct[k]),
                                       rtol=1e-6, atol=1e-7)

    def test_bf16_params_keep_f32_grads(self):
        """Master-free regression: the front end must flatten grads in f32
        — the engine accumulates them in f32 over bf16 params, and a cast
        to the param-group dtype would truncate them before the kernel's
        f32 moment update ever sees them."""
        g_val = 1.0 + 1 / 4096            # NOT bf16-representable
        params = {"w": jnp.full((64,), 0.5, jnp.bfloat16)}
        g = {"w": jnp.full((64,), g_val, jnp.float32)}
        fus = fused_adam(_sched, B1, B2, EPS, 0.0)
        _, fs = jax.jit(fus.fused_apply)(g, fus.init(params), params)
        mv, vv = leaf_moment_views(fs, params)
        np.testing.assert_allclose(np.asarray(mv["w"]),
                                   np.float32((1 - B1) * g_val), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(vv["w"]),
                                   np.float32((1 - B2) * g_val ** 2),
                                   rtol=1e-5)

    def test_stochastic_rounding_in_kernel(self):
        """bf16 params + sr_key: the write lands on a bf16 neighbor of the
        f32 result (within one bf16 ulp), moments stay f32, and distinct
        seeds produce distinct roundings."""
        params = _tree(7, dtype=jnp.bfloat16)
        fus = fused_adam(_sched, B1, B2, EPS, WD)
        fs = fus.init(params)
        g = _grads(0, params)
        gb = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), g)
        apply = jax.jit(lambda g, s, p, k: fus.fused_apply(g, s, p,
                                                           sr_key=k))
        p_sr, fs_sr = apply(gb, fs, params, jax.random.PRNGKey(0))
        p_sr2, _ = apply(gb, fs, params, jax.random.PRNGKey(1))
        # deterministic f32 reference of the same update
        p32 = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), params)
        f32 = fused_adam(_sched, B1, B2, EPS, WD)
        p_ref, _ = jax.jit(f32.fused_apply)(gb, f32.init(p32), p32)
        any_diff = False
        for k in params:
            assert p_sr[k].dtype == jnp.bfloat16
            a = np.asarray(p_sr[k], np.float32)
            r = np.asarray(p_ref[k], np.float32)
            # one bf16 ulp at the reference's magnitude
            ulp = np.maximum(np.abs(r), 1e-30) * 2 ** -7
            assert np.all(np.abs(a - r) <= ulp + 1e-7), k
            any_diff |= not np.array_equal(
                np.asarray(p_sr[k], np.float32),
                np.asarray(p_sr2[k], np.float32))
        assert any_diff, "distinct seeds must round differently somewhere"
        assert fs_sr.m[0].dtype == jnp.float32


@pytest.mark.usefixtures("layout")
class TestOnePassStep:
    """fused_step: norm + clip + overflow + cast all inside the single
    HBM pass, vs the historical two-pass sequencing."""

    def test_matches_two_pass_clip(self):
        """fused_step(clip=c) == global_norm + clip_coefficient +
        fused_apply(clip_coeff=...) — the two paths share the clip
        expression textually, so parity is tight."""
        from deepspeed_tpu.runtime.utils import clip_coefficient, global_norm
        params = _tree(8)
        clip = 0.5
        fus = fused_adam(_sched, B1, B2, EPS, WD)
        fs = fus.init(params)
        g = _grads(0, params)
        out = jax.jit(lambda g, s, p: fus.fused_step(g, s, p, clip=clip))(
            g, fs, params)
        norm = global_norm(g)
        coeff = clip_coefficient(norm, clip)
        p_two, fs_two = jax.jit(lambda g, s, p, c: fus.fused_apply(
            g, s, p, clip_coeff=c))(g, fs, params, coeff)
        np.testing.assert_allclose(float(out.grad_norm), float(norm),
                                   rtol=1e-6)
        assert not bool(out.overflow)
        for k in params:
            np.testing.assert_allclose(np.asarray(out.params[k]),
                                       np.asarray(p_two[k]),
                                       rtol=1e-6, atol=1e-7)
        # moments track g*coeff; the one-pass norm sums chunk partials in
        # a different association than per-leaf global_norm, so coeff (and
        # hence m) agrees to f32 ulp, not bitwise (PR-1 precedent).
        np.testing.assert_allclose(np.asarray(out.state.m[0]),
                                   np.asarray(fs_two.m[0]),
                                   rtol=1e-6, atol=1e-9)
        assert int(out.state.count) == 1

    def test_fp16_overflow_holds_step_in_kernel(self):
        """An inf gradient under fp16: the in-pass vote (non-finite sum
        of squares) holds params/moments bit-identically and the count
        does not advance — no separate tree_has_inf_or_nan read."""
        params = _tree(9)
        fus = fused_adam(_sched, B1, B2, EPS, WD)
        fs = fus.init(params)
        g = _grads(0, params)
        g = dict(g, b=jnp.asarray(np.inf, jnp.float32))
        out = jax.jit(lambda g, s, p: fus.fused_step(
            g, s, p, clip=1.0, inv_scale=jnp.float32(1 / 128.0),
            fp16=True))(g, fs, params)
        assert bool(out.overflow)
        assert int(out.state.count) == 0
        for k in params:
            np.testing.assert_array_equal(np.asarray(out.params[k]),
                                          np.asarray(params[k]))
        np.testing.assert_array_equal(np.asarray(out.state.m[0]),
                                      np.asarray(fs.m[0]))

    def test_fp16_unscale_in_kernel(self):
        """fused_step(inv_scale=1/s) on scale-multiplied grads equals
        fused_step on the unscaled grads (norm included: ||g*s||/s)."""
        params = _tree(10)
        fus = fused_adam(_sched, B1, B2, EPS, WD)
        fs = fus.init(params)
        g = _grads(0, params)
        s = 1024.0
        g_scaled = jax.tree_util.tree_map(lambda x: x * s, g)
        a = jax.jit(lambda g, st, p: fus.fused_step(
            g, st, p, clip=1.0, inv_scale=jnp.float32(1.0 / s),
            fp16=True))(g_scaled, fs, params)
        b = jax.jit(lambda g, st, p: fus.fused_step(g, st, p, clip=1.0))(
            g, fs, params)
        np.testing.assert_allclose(float(a.grad_norm), float(b.grad_norm),
                                   rtol=1e-6)
        for k in params:
            np.testing.assert_allclose(np.asarray(a.params[k]),
                                       np.asarray(b.params[k]),
                                       rtol=1e-5, atol=1e-7)

    def test_cast_refresh_in_pass(self):
        """cast_dtype=bf16: the compute-dtype copy comes out of the same
        kernel write and equals an explicit post-apply cast; non-float
        leaves pass through untouched."""
        params = dict(_tree(11), idx=jnp.arange(3, dtype=jnp.int32))
        fus = fused_adam(_sched, B1, B2, EPS, WD)
        fs = fus.init(params)
        g = dict(_grads(0, {k: v for k, v in params.items() if k != "idx"}),
                 idx=jnp.zeros((3,), jnp.int32))
        out = jax.jit(lambda g, s, p: fus.fused_step(
            g, s, p, clip=1.0, cast_dtype=jnp.bfloat16))(g, fs, params)
        assert out.cast_params is not None
        for k in ("w", "big", "b"):
            assert out.cast_params[k].dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(out.cast_params[k], np.float32),
                np.asarray(out.params[k].astype(jnp.bfloat16), np.float32))
        np.testing.assert_array_equal(np.asarray(out.cast_params["idx"]),
                                      np.asarray(params["idx"]))

    def test_no_norm_requested(self):
        """clip=0, fp16 off, compute_norm off: grad_norm reports -1 (the
        no-extra-HBM-pass sentinel) and the update is the plain apply."""
        params = _tree(12)
        fus = fused_adam(_sched, B1, B2, EPS, WD)
        fs = fus.init(params)
        g = _grads(0, params)
        out = jax.jit(lambda g, s, p: fus.fused_step(
            g, s, p, compute_norm=False))(g, fs, params)
        assert float(out.grad_norm) == -1.0
        p_ref, _ = jax.jit(fus.fused_apply)(g, fs, params)
        for k in params:
            np.testing.assert_array_equal(np.asarray(out.params[k]),
                                          np.asarray(p_ref[k]))


@pytest.mark.parametrize("sr", [False, True], ids=["round", "sr"])
@pytest.mark.parametrize("clip", [0.0, 0.05], ids=["noclip", "clip"])
def test_narrow_grads_equal_their_widening(layout, clip, sr):
    """Gradients at the width a bf16 backward writes them give the update
    the same values widened to f32 give: widening is exact, so WHERE it
    happens (the kernel's first line and the norm's reduction for an
    in-place leaf, the flatten for a packed one) changes no value. With
    clip off every bit of p, m and v agrees; with clip on the one
    permitted difference is the association of the f32 sum of squares, a
    few ulp of the norm. A second step reads non-zero moments: there the
    two COMPILED programs may differ by the CPU compiler's FMA
    contraction of ``(1-b)*g + b*m`` (this file's header; one rounding
    of a term, seen on in-place leaves), which no operand width causes
    on the chip — Mosaic's v5e has no fused multiply-add, and
    PERF.md's PR 49 entry holds the chip's bits over three steps."""
    params = _tree(21, dtype=jnp.bfloat16)
    fus = fused_adam(_sched, B1, B2, EPS, WD)
    step = jax.jit(lambda g, s, p, k: fus.fused_step(
        g, s, p, clip=clip, compute_norm=clip > 0, sr_key=k))

    def run(width):
        p, st, outs = params, fus.init(params), []
        for i in range(2):
            g = jax.tree_util.tree_map(lambda x: x.astype(width),
                                       _grads(i, params))
            out = step(g, st, p, jax.random.PRNGKey(5 + i) if sr else None)
            p, st = out.params, out.state
            outs.append((out,) + leaf_moment_views(st, params))
        return outs

    for i, ((a, ma, va), (b, mb, vb)) in enumerate(
            zip(run(jnp.bfloat16), run(jnp.float32))):
        if clip:
            assert float(a.grad_norm) > clip    # the clip really engaged
            np.testing.assert_allclose(float(a.grad_norm),
                                       float(b.grad_norm), rtol=1e-6)
        for k in params:
            assert a.params[k].dtype == jnp.bfloat16
            got = [np.asarray(x[k], np.float32) for x in (a.params, ma, va)]
            want = [np.asarray(x[k], np.float32)
                    for x in (b.params, mb, vb)]
            for x, y, rtol in zip(got, want, (2.0 ** -7, 1e-5, 1e-5)):
                if clip == 0 and (i == 0 or x is got[0]):
                    np.testing.assert_array_equal(x, y)
                else:
                    # one rounding of a TERM of the moment's sum (clip:
                    # a few ulp of the coefficient; p: one bf16 ulp)
                    np.testing.assert_allclose(
                        x, y, rtol=rtol if clip else 1e-6,
                        atol=(1e-5 if clip else 1e-6) * np.abs(y).max())


# ------------------------------------------------------------------ #
# Engine tier — 8-device CPU mesh, ZeRO-2
# ------------------------------------------------------------------ #
DIM = 32
_W_TRUE = np.random.default_rng(0).standard_normal(DIM).astype(np.float32)


def loss_fn(params, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def make_batch(i, n=64):
    r = np.random.default_rng(i)
    x = r.standard_normal((n, DIM)).astype(np.float32)
    return {"x": jnp.asarray(x), "y": jnp.asarray(x @ _W_TRUE)}


def _params():
    return {"w": jnp.zeros((DIM,), jnp.float32),
            "b": jnp.zeros((), jnp.float32)}


def _cfg(fused, gas=1, **over):
    cfg = {
        "train_batch_size": 64,
        "train_micro_batch_size_per_gpu": 64 // (8 * gas),
        "gradient_accumulation_steps": gas,
        "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-2, "fused": fused}},
        "zero_optimization": {"stage": 2},
        "bf16": {"enabled": True},
        "steps_per_print": 10 ** 9,
    }
    cfg.update(over)
    return cfg


def _run(cfg, steps=6):
    eng = DeepSpeedEngine(model=loss_fn, model_params=_params(),
                          config=cfg, mesh=build_mesh())
    losses = [float(jax.device_get(eng.train_batch(make_batch(i))))
              for i in range(steps)]
    return eng, losses


def test_config_knob_selects_path():
    eng_f, _ = _run(_cfg(True), steps=1)
    eng_o, _ = _run(_cfg(False), steps=1)
    assert eng_f._fused_apply is not None
    assert isinstance(eng_f.state.opt_state, FusedAdamState)
    assert eng_o._fused_apply is None
    assert not isinstance(eng_o.state.opt_state, FusedAdamState)
    # default is ON for the Adam family
    cfg = _cfg(True)
    del cfg["optimizer"]["params"]["fused"]
    eng_d, _ = _run(cfg, steps=1)
    assert eng_d._fused_apply is not None
    assert eng_d.config.optimizer_fused


def test_engine_parity_fp32_master():
    """bf16 compute + fp32 masters + clipping + ZeRO-2 over dp=8: fused and
    optax trajectories agree to f32-ulp accumulation tolerance."""
    eng_f, l_f = _run(_cfg(True))
    eng_o, l_o = _run(_cfg(False))
    np.testing.assert_allclose(l_f, l_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(eng_f.state.params["w"]),
        np.asarray(eng_o.state.params["w"]), rtol=1e-5, atol=1e-6)


def test_engine_parity_gas_scan_path():
    eng_f, l_f = _run(_cfg(True, gas=2))
    eng_o, l_o = _run(_cfg(False, gas=2))
    np.testing.assert_allclose(l_f, l_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(eng_f.state.params["w"]),
        np.asarray(eng_o.state.params["w"]), rtol=1e-5, atol=1e-6)


def test_engine_parity_master_free_sr():
    """Master-free bf16 + stochastic rounding (seeded): both paths round
    the same f32 trajectory, so params agree to bf16 tolerance and the
    state really is bf16 (no fp32 master anywhere)."""
    bf16 = {"enabled": True, "stochastic_rounding": True}
    eng_f, l_f = _run(_cfg(True, bf16=bf16))
    eng_o, l_o = _run(_cfg(False, bf16=bf16))
    assert eng_f.state.params["w"].dtype == jnp.bfloat16
    assert eng_o.state.params["w"].dtype == jnp.bfloat16
    np.testing.assert_allclose(l_f, l_o, rtol=0.2, atol=0.05)
    np.testing.assert_allclose(
        np.asarray(eng_f.state.params["w"], np.float32),
        np.asarray(eng_o.state.params["w"], np.float32),
        rtol=0.05, atol=0.05)
    # and the run learns (the SR mode's whole point)
    assert l_f[-1] < 0.5 * l_f[0]


@pytest.mark.parametrize("old_tag", [None, 2])
def test_older_layout_checkpoint_refused(tmp_path, old_tag):
    """A fused-optimizer checkpoint of an older moment layout — no
    fused_moment_layout marker (pre-ISSUE-8: end-to-end leaf
    concatenation) or 2 (every leaf in the V-interleaved buffers) — must
    be refused loudly: the flat sizes can coincide and a structural
    restore would silently scramble moments across leaves."""
    import json as _json
    import os as _os
    from deepspeed_tpu.runtime.engine import FUSED_MOMENT_LAYOUT
    eng, _ = _run(_cfg(True), steps=1)
    eng.save_checkpoint(str(tmp_path), tag="t")
    mf = _os.path.join(str(tmp_path), "t", "engine_meta.json")
    with open(mf) as f:
        meta = _json.load(f)
    assert meta["fused_moment_layout"] == FUSED_MOMENT_LAYOUT == 3
    if old_tag is None:
        del meta["fused_moment_layout"]
    else:
        meta["fused_moment_layout"] = old_tag
    with open(mf, "w") as f:
        _json.dump(meta, f)
    eng2, _ = _run(_cfg(True), steps=1)
    with pytest.raises(ValueError, match="fused_moment_layout"):
        eng2.load_checkpoint(str(tmp_path), tag="t")
    # params-only restore stays available
    eng2.load_checkpoint(str(tmp_path), tag="t",
                         load_optimizer_states=False)


def test_engine_fused_checkpoint_roundtrip(tmp_path):
    """Fused opt state (flat chunk buffers) survives the sharded
    checkpoint save/load with the trajectory intact."""
    eng, _ = _run(_cfg(True), steps=3)
    eng.save_checkpoint(str(tmp_path), tag="t3")
    eng2, _ = _run(_cfg(True), steps=1)
    eng2.load_checkpoint(str(tmp_path), tag="t3")
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(eng.state.opt_state.m[0])),
        np.asarray(jax.device_get(eng2.state.opt_state.m[0])))
    l1 = float(jax.device_get(eng.train_batch(make_batch(100))))
    l2 = float(jax.device_get(eng2.train_batch(make_batch(100))))
    assert abs(l1 - l2) < 1e-6, (l1, l2)


# ------------------------------------------------------------------ #
# The one-device step: gradients reach the kernel as the backward wrote them
# ------------------------------------------------------------------ #
_WIDE, _DEEP = 1024, 512          # 2**19 elements: the plan's in-place floor


def _mlp_loss(params, batch, rng):
    h = jnp.tanh(batch["x"].astype(params["w1"].dtype) @ params["w1"]
                 + params["b1"])
    return jnp.mean(jnp.square((h @ params["w2"]).astype(jnp.float32)
                               - batch["y"]))


def _walk(jaxpr, stack=""):
    """(eqn, scope path) over a jaxpr and every jaxpr under it; an inner
    jaxpr's name stack is relative to the equation that holds it."""
    from deepspeed_tpu.analysis.passes import _subjaxprs
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        yield eqn, here
        for inner in _subjaxprs(eqn):
            yield from _walk(inner, here)


@pytest.mark.parametrize("gas,narrow", [(1, True), (2, False)],
                         ids=["one_micro_batch", "accumulation_scan"])
def test_one_device_step_hands_the_kernel_bf16_grads(gas, narrow):
    """The master-free one-device step, lowered: with one micro-batch
    every in-place ``_fused_adam_kernel`` call reads a bf16 gradient and
    no bf16 -> f32 widening of a parameter-shaped array is left outside
    the ``optimizer`` scope (a Pallas call is opaque to XLA's fusion: a
    widening ahead of it is a materialized pass); the accumulation scan
    sums in f32 and hands the kernel what it did before. The pricing
    follows the operand."""
    r = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(r.standard_normal((_DEEP, _WIDE)) * 0.02),
        "b1": jnp.zeros((_WIDE,)),
        "w2": jnp.asarray(r.standard_normal((_WIDE, _DEEP)) * 0.02),
    }
    cfg = _cfg(True, gas=gas, train_micro_batch_size_per_gpu=8 // gas,
               train_batch_size=8,
               bf16={"enabled": True, "stochastic_rounding": True},
               zero_optimization={"stage": 0})
    eng = DeepSpeedEngine(model=_mlp_loss, model_params=params, config=cfg,
                          mesh=build_mesh(devices=jax.devices()[:1]))
    assert eng._grads_stay_narrow() == narrow
    batch = {"x": jnp.zeros((8, _DEEP)), "y": jnp.zeros((8, _DEEP))}
    eng._prepare_batch(batch, None)      # builds the step, runs nothing
    traced = eng._build_train_step().trace(
        eng.state, batch, jax.random.PRNGKey(0))
    big = {tuple(params[k].shape) for k in ("w1", "w2")}
    leaf_calls, kernel_g, stray = [], [], []
    for eqn, scope in _walk(traced.jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in ("pjit", "jit") and eqn.params["name"] == "_update_leaf":
            leaf_calls.append(eqn.invars[0].aval.dtype)
            kernel_g += [e.invars[2].aval.dtype
                         for e, _ in _walk(eqn.params["jaxpr"].jaxpr)
                         if e.primitive.name == "pallas_call"]
        if name == "convert_element_type" and \
                tuple(eqn.invars[0].aval.shape) in big and \
                eqn.invars[0].aval.dtype == jnp.bfloat16 and \
                eqn.outvars[0].aval.dtype == jnp.float32 and \
                "optimizer" not in scope:
            stray.append(scope)
    want = jnp.bfloat16 if narrow else jnp.float32
    assert leaf_calls == [want, want], leaf_calls
    assert kernel_g == [want, want], kernel_g
    if narrow:
        assert not stray, stray
    priced = eng._optimizer_apply_pricing()["per_replica"]["one_pass"]
    n_big, n_small = 2 * _DEEP * _WIDE, _WIDE
    g_width = 2 if narrow else 4
    # kernel: g + p read, p written, m and v read and written; then the
    # norm's second read of g (clip is on); packed leaves flatten in f32
    assert priced == n_big * (2 * g_width + 2 + 2 + 16) \
        + n_small * (2 * 4 + 2 + 2 + 16)
