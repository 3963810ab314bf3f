"""The chunked-prefill program's row widths (ISSUE 43): ``prefill_step`` is
compiled at ``prefill_chunk`` and its half (no narrower than 128 rows),
each before the first admission, and a dispatch takes the narrowest that
holds its rows.

What is held to what, all on CPU at tiny model widths but ``prefill_chunk``
512, so that the ladder is the real one:
(a) the ladder from the shapes;
(b) the five served families: prompts whose tails select each width give the
    first token, the logits and the cache of the same engine pinned to the
    single width 512; a model that freezes its state inside a chunk (ISSUE
    46) runs a turn as ONE program where the cut ran two;
(c) ``dp`` = 2: a dispatch is as wide as its longest active group needs;
(d) once the first ``serve()`` has begun nothing compiles, whatever the
    widths the prompts select, and another shape still raises;
(e) ``rows_computed``, the ``prefill_chunk`` spans' ``rows``,
    ``snapshot()["prefill_width_dispatches"]`` and the aggregator's
    ``prefill_rows_computed`` agree with the widths dispatched;
(f) with the compile cache on, a second start loads the narrow width's
    executable and neither traces nor lowers it; a file that does not
    load, and a change of the program, build it again.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.monitor.recompile import RecompileError
from deepspeed_tpu.monitor.serving import COL
from deepspeed_tpu.parallel.topology import build_mesh

CHUNK, MAX_LEN, BS = 512, 1024, 16
LADDER = (256, 512)


@pytest.fixture(autouse=True)
def no_programs_dir(monkeypatch):
    """No executable is kept between these tests' engines, wherever the
    environment puts JAX's compile cache ((f) gives itself a directory)."""
    monkeypatch.setattr(engine_mod, "_programs_dir", lambda: None)


# --------------------------------------------------------------------- #
# (a) The ladder from the shapes
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("chunk,block,want", [
    (512, 64, (256, 512)),
    (512, 16, (256, 512)),
    (256, 64, (128, 256)),
    (128, 16, (128,)),              # cell 2: the programs it had
    (96, 16, (96,)),                # a halving under 128 rows: none
    (8, 16, (8,)),                  # the CPU tests' chunks
    (512, 512, (512,)),             # 256 is no multiple of the block
    (1024, 64, (512, 1024)),        # two widths at most
    (384, 64, (192, 384)),
    (640, 64, (320, 640)),
    (640, 128, (640,)),             # 320 is no multiple of 128
    (2048, 64, (1024, 2048)),       # the first halving, whatever the chunk
])
def test_the_ladder_from_the_shapes(chunk, block, want):
    assert engine_mod.prefill_widths(chunk, block) == want


def test_the_floor_is_the_chips_gemm_ridge():
    # 197 TFLOP/s over 819 GB/s = 240 FLOP a byte = 240 rows of a bf16
    # GEMM: at 128 rows the weights' bytes bound it already.
    assert engine_mod.MIN_PREFILL_WIDTH == 128 < 197e12 / 819e9 < 256
    assert engine_mod.MAX_PREFILL_WIDTHS == 2


# --------------------------------------------------------------------- #
# The five served families at tiny widths
# --------------------------------------------------------------------- #
def _gpt2():
    from deepspeed_tpu.models.gpt2 import GPT2_CONFIGS, gpt2_init
    cfg = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"], dtype=jnp.float32,
                              max_seq_length=MAX_LEN)
    return cfg, gpt2_init(jax.random.PRNGKey(0), cfg), cfg.vocab_size, \
        {"num_blocks": 160}


def _latent():
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                                  deepseek_v3_init)
    cfg = DeepseekV3Config(
        vocab_size=250, vocab_rows_held=256, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, first_k_dense_replace=1,
        num_attention_heads=4, n_routed_experts=16, held=(0, 16),
        num_experts_per_tok=4, n_group=4, topk_group=2, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, max_position_embeddings=MAX_LEN,
        rope_original_max_position_embeddings=32, rope_factor=8.0,
        dtype=jnp.float32, initializer_range=0.08)
    return cfg, deepseek_v3_init(jax.random.PRNGKey(0), cfg), 250, \
        {"num_blocks": 160}


def _retention():
    from deepspeed_tpu.models.brumby import BrumbyConfig, brumby_init
    sizes = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
                 max_position_embeddings=MAX_LEN, rope_theta=1e6,
                 assumed=dict(retention_power=2, retention_eps=1e-6))
    cfg = BrumbyConfig.from_hf(sizes, dtype=jnp.float32,
                               gate_half_life_min=4.0,
                               gate_half_life_max=256.0)
    return cfg, brumby_init(jax.random.PRNGKey(0), cfg), 128, \
        {"num_blocks": 8}


def _classes():
    from deepspeed_tpu.models.afmoe import AfmoeConfig, afmoe_init
    cfg = AfmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, sliding_window=8,
        max_position_embeddings=MAX_LEN, dtype=jnp.float32,
        initializer_range=0.08)
    return cfg, afmoe_init(jax.random.PRNGKey(0), cfg), 128, \
        {"num_blocks": {"full": 160, "window": 136}}


def _kinds():
    from deepspeed_tpu.models.lfm2 import lfm2_init
    from test_lfm2_serving import tiny
    cfg = tiny(max_position_embeddings=MAX_LEN)
    return cfg, lfm2_init(jax.random.PRNGKey(0), cfg), 128, \
        {"num_blocks": {"full": 160, "conv": 8}}


FAMILIES = {"gpt2": _gpt2, "latent": _latent, "retention": _retention,
            "two_classes": _classes, "two_kinds": _kinds}


def _engine(cfg, params, extra, mesh=None, telemetry=None, **inference):
    conf = dict(max_slots=2, max_seq_len=MAX_LEN, block_size=BS,
                prefill_chunk=CHUNK, paged_kernel=False)
    conf.update(extra)
    conf.update(inference)
    config = {"inference": conf}
    if telemetry:
        config["telemetry"] = telemetry
    return InferenceEngine(
        cfg, params, config=config,
        mesh=mesh or build_mesh(devices=jax.devices()[:1]))


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(0, vocab, size=n,
                                                dtype=np.int32)


def _through(eng, prompt):
    """(first token, prefill logits, first-decode logits, admission)."""
    slot = eng.select_slot(prompt, 2)
    tok, pre = eng.prefill(prompt, slot, return_logits=True,
                           max_new_tokens=2)
    info = dict(eng.last_admit_info(slot))
    eng.activate_slot(slot, len(prompt), tok)
    _, dec = eng.decode_once(return_logits=True)
    eng.release_slot(slot)
    return tok, np.asarray(pre), np.asarray(dec[slot]), info


# --------------------------------------------------------------------- #
# (b) Every width gives what the single width 512 gives
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_width_gives_what_the_single_width_gives(family, monkeypatch):
    cfg, params, vocab, extra = FAMILIES[family]()
    ladder = _engine(cfg, params, extra)
    assert ladder.prefill_widths == LADDER
    monkeypatch.setattr(engine_mod, "prefill_widths",
                        lambda chunk, block: (chunk,))
    pinned = _engine(cfg, params, extra)
    monkeypatch.undo()
    assert pinned.prefill_widths == (CHUNK,)

    # A document of 700 tokens runs 512 + 188 rows (widths 512, 256) — with
    # a per-stream state that only the end of a program can freeze (the
    # retention family's) its snapshot is due at 688 and the tail is cut
    # there: 512, 176 and 12 rows (512, 256, 256). Then tails that select
    # each width alone (such a state's 200 rows are cut at 192 for a
    # snapshot: 256, 256), and a question over the cached document (its
    # prefix, or its snapshot: the tail is the remainder + the question).
    # A conv state beside pages (``two_kinds``) is frozen by the program
    # that passes the boundary: no cut, the widths of a model without one.
    state = family == "retention"
    doc = _tokens(1, 700, vocab)
    prompts = [doc, _tokens(2, 70, vocab), _tokens(3, 200, vocab),
               _tokens(4, 400, vocab),
               np.concatenate([doc, _tokens(5, 60, vocab)])]
    want_widths = [[512, 256, 256] if state else [512, 256], [256],
                   [256, 256] if state else [256], [512], [256]]
    for prompt, widths in zip(prompts, want_widths):
        before = dict(ladder.serving.prefill_width_dispatches)
        got = _through(ladder, prompt)
        want = _through(pinned, prompt)
        ran = ladder.serving.prefill_width_dispatches
        assert sorted(w for w in ran
                      for _ in range(ran[w] - before.get(w, 0))) \
            == sorted(widths), (len(prompt), got[3])
        assert got[3] == want[3]                 # the same admission
        assert got[0] == want[0]
        np.testing.assert_allclose(got[1], want[1], atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(got[2], want[2], atol=2e-5, rtol=1e-5)
    # The cache: every pool row for row (the dead rows of a wide program
    # and the warm dispatches of the ladder wrote nothing).
    assert list(ladder.cache) == list(pinned.cache)
    for name in ladder.cache:
        np.testing.assert_allclose(np.asarray(ladder.cache[name]),
                                   np.asarray(pinned.cache[name]),
                                   atol=2e-5, rtol=1e-5, err_msg=name)
    assert (ladder.block_tables == pinned.block_tables).all()
    assert set(pinned.serving.prefill_width_dispatches) == {CHUNK}
    ladder.close()
    pinned.close()


def test_a_question_over_a_snapshot_resumes_and_takes_the_narrow_program():
    cfg, params, vocab, extra = _retention()
    eng = _engine(cfg, params, extra)
    doc = _tokens(1, 700, vocab)
    _through(eng, doc)
    *_, info = _through(eng, np.concatenate([doc, _tokens(5, 60, vocab)]))
    assert info["cached_tokens"] == 688 and info["chunks"] == 1
    assert info["cow_fork"]                  # the snapshot's page, copied
    assert eng.serving.prefill_width_dispatches == {512: 1, 256: 3}
    eng.close()


def test_a_turn_over_a_conv_state_is_one_program_and_no_snapshot_copy(
        tmp_path):
    """A model that freezes its state inside a chunk (ISSUE 46): a turn of
    up to ``prefill_chunk`` new tokens dispatches ONE chunk program, which
    leaves the snapshot, and one page copy (the snapshot it resumed from,
    into its own page) — where the same model made to say no runs the cut's
    second program and a second copy."""
    import json
    from test_lfm2_serving import CutServed as Cut
    cfg, params, vocab, extra = _kinds()
    history = _tokens(1, 300, vocab)
    turn = np.concatenate([history, _tokens(2, 400, vocab)])
    found = {}
    for name, model in (("program", cfg), ("cut", Cut(cfg))):
        trace_path = str(tmp_path / f"{name}.trace.json")
        eng = _engine(model, params, extra, telemetry={
            "enabled": True, "output_path": str(tmp_path), "job_name": name,
            "report_steps": 10 ** 6, "trace_path": trace_path})
        out = [_through(eng, p) for p in (history, turn)]
        page = eng.cache_specs[-1].block_nbytes()
        state = eng.serving.snapshot()["state"]
        found[name] = (out, dict(eng.serving.prefill_width_dispatches),
                       state, page)
        eng.close()
        found[name] += ([e["args"] for e in json.load(open(trace_path))
                         if e.get("name") == "prefill"],)
    out, ran, state, page, spans = found["program"]
    cout, cran, cstate, _, cspans = found["cut"]
    for (tok, pre, dec, info), (ctok, cpre, cdec, cinfo) in zip(out, cout):
        assert tok == ctok
        np.testing.assert_allclose(pre, cpre, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(dec, cdec, atol=2e-5, rtol=1e-5)
        assert info["snapshot_at"] == cinfo["snapshot_at"] > 0
        assert info["cached_tokens"] == cinfo["cached_tokens"]
    assert [o[3]["snapshot_at"] for o in out] == [288, 688]
    assert out[1][3]["cached_tokens"] == 288 and out[1][3]["cow_fork"]
    # the history's 300 rows and the turn's 412: one 512-row program each;
    # cut at 288 / 688, 12 rows more in a program of their own
    assert [o[3]["chunks"] for o in out] == [1, 1] and ran == {512: 2}
    assert [o[3]["chunks"] for o in cout] == [2, 2] \
        and cran == {512: 2, 256: 2}
    assert [a["chunks"] for a in spans] == [1, 1]
    assert [a["chunks"] for a in cspans] == [2, 2]
    assert [(a["snapshot_taken"], a["snapshot_in_program"]) for a in spans] \
        == [(1, 1), (1, 1)]
    assert [(a["snapshot_taken"], a["snapshot_in_program"])
            for a in cspans] == [(1, 0), (1, 0)]
    # bytes of the copies DISPATCHED: the turn's halve, the history's none
    assert [a["state_copy_bytes"] for a in spans] == [0, 2 * page]
    assert [a["state_copy_bytes"] for a in cspans] == [2 * page, 4 * page]
    assert (state["snapshots_taken"], state["snapshots_in_program"]) == (2, 2)
    assert (cstate["snapshots_taken"], cstate["snapshots_in_program"]) \
        == (2, 0)
    assert state["state_copy_bytes"] == 2 * page \
        and cstate["state_copy_bytes"] == 6 * page


# --------------------------------------------------------------------- #
# (c) dp = 2: as wide as the longest active group needs
# --------------------------------------------------------------------- #
def test_a_dispatch_is_as_wide_as_its_longest_active_group():
    cfg, params, vocab, extra = _gpt2()
    if len(jax.devices()) < 2:
        pytest.skip("needs two host devices")
    eng = _engine(cfg, params, extra,
                  mesh=build_mesh(dp=2, devices=jax.devices()[:2]),
                  max_slots=4, num_blocks=320)
    assert eng.dp == 2 and eng.prefill_widths == LADDER
    one = _engine(cfg, params, extra)

    def admit(lengths, seed):
        prompts = [_tokens(seed + i, n, vocab)
                   for i, n in enumerate(lengths)]
        slots = []
        for p in prompts:
            slots.append(eng.select_slot(p, 2, exclude_groups={
                eng.group_of(s) for s in slots}))
        before = dict(eng.serving.prefill_width_dispatches)
        out = eng.prefill_many([(s, p, 2) for s, p in zip(slots, prompts)],
                               return_logits=True)
        ran = eng.serving.prefill_width_dispatches
        for (tok, logits), s, p in zip(out, slots, prompts):
            tok1, pre1, _, _ = _through(one, p)
            assert tok == tok1
            np.testing.assert_allclose(logits, pre1, atol=2e-5, rtol=1e-5)
            eng.activate_slot(s, len(p), tok)
            eng.release_slot(s)
        return {w: ran[w] - before.get(w, 0) for w in ran
                if ran[w] - before.get(w, 0)}

    assert admit([100, 300], 10) == {512: 1}        # the longer group's
    assert admit([100, 200], 20) == {256: 1}
    assert admit([256, 257], 30) == {512: 1}
    # 600 and 100 rows: chunk 0 holds 512 and 100 (512 wide); chunk 1 only
    # the first group's 88 rows, the other group inactive (256 wide)
    assert admit([600, 100], 40) == {512: 1, 256: 1}
    eng.close()
    one.close()


# --------------------------------------------------------------------- #
# (d) Nothing compiles once the first serve() has begun
# --------------------------------------------------------------------- #
def _requests(vocab, lengths, seed, rid0=0):
    return [Request(rid=rid0 + i, prompt=_tokens(seed + i, n, vocab),
                    max_new_tokens=3, arrival_s=0.0)
            for i, n in enumerate(lengths)]


def test_no_width_compiles_after_the_first_serve_has_begun(tmp_path):
    import jax.monitoring
    from jax._src import monitoring
    cfg, params, vocab, extra = _gpt2()
    eng = _engine(cfg, params, extra, telemetry={
        "enabled": True, "output_path": str(tmp_path), "job_name": "w",
        "report_steps": 10 ** 6, "fail_on_recompile": True})
    compiles = []

    def listener(name, *_, **__):
        if "backend_compile" in name:
            compiles.append(name)
    # Built and not served: no prefill program yet (tier-1's engines).
    assert eng.telemetry.sentinel.compile_counts()["prefill_step"] == 0
    report = eng.serve(_requests(vocab, [20], seed=1))
    assert report["completed"] == 1
    # One short prompt made every width (and decode, twice: its first
    # dispatch takes zeros for the fetch before).
    counts = eng.telemetry.sentinel.compile_counts()
    assert counts["prefill_step"] == len(LADDER)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        report = eng.serve(_requests(
            vocab, [100, 200, 400, 600, 128, 129, 256, 257, 512, 513],
            seed=50, rid0=10))
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert report["completed"] == 11 and report["recompiles"] == 0
    assert compiles == []
    assert eng.telemetry.recompile_count == 0
    assert eng.telemetry.sentinel.compile_counts() == counts
    ran = eng.serving.snapshot()["prefill_width_dispatches"]
    assert set(ran) == set(LADDER)

    # The audit re-lowers the program from the sentinel's registry (the
    # last width that compiled: the narrowest).
    lint = eng.lint_audit(passes=("host_sync", "materialization"))
    assert {p.name for p in lint.paths} == {"decode_step", "prefill_step"}
    assert not lint.unwaived and not any(p.errors for p in lint.paths)

    # Any OTHER shape is still a recompile, and raises.
    G, J = eng.dp, eng.allocator.table_width
    zeros = np.zeros(G, np.int32)
    *pools, _, _ = eng._prefill_fn(
        eng._params, *eng._pools(), np.zeros((G, 64), np.int32),
        np.full((G, J), -1, np.int32), zeros, zeros, zeros,
        np.int32(0), eng._next_key(), np.float32(0.0))
    eng._store_pools(pools)
    with pytest.raises(RecompileError, match="prefill_step"):
        eng.telemetry.raise_pending()
    eng.close()


def test_one_width_warms_nothing_and_keeps_the_sentinels_warmup():
    """``prefill_chunk`` 128 (cell 2) and the tiny chunks of the CPU tests:
    one width, no warm dispatch, the programs they had."""
    cfg, params, vocab, extra = _gpt2()
    eng = _engine(cfg, params, extra, prefill_chunk=128, telemetry={
        "enabled": True, "fail_on_recompile": True,
        "report_steps": 10 ** 6})
    assert eng.prefill_widths == (128,) and eng._prefill_warmed
    _through(eng, _tokens(1, 300, vocab))            # three chunks
    assert eng.telemetry.sentinel.compile_counts()["prefill_step"] == 1
    st = eng.telemetry.sentinel._fns["prefill_step"]
    assert st["calls"] == 3
    assert eng.serving.prefill_width_dispatches == {128: 3}
    eng.close()


# --------------------------------------------------------------------- #
# (e) The counters agree with the widths dispatched
# --------------------------------------------------------------------- #
def test_the_counters_agree_with_the_dispatched_widths(tmp_path):
    import json
    cfg, params, vocab, extra = _gpt2()
    trace_path = str(tmp_path / "host.trace.json")
    eng = _engine(cfg, params, extra, telemetry={
        "enabled": True, "output_path": str(tmp_path), "job_name": "w",
        "report_steps": 10 ** 6, "trace_path": trace_path})
    # (1000 rows: 512 + 488 -> 512, 512; 600: 512 + 88 -> 512, 256)
    want = {100: [256], 200: [256], 400: [512], 600: [512, 256],
            1000: [512, 512]}
    report = eng.serve(_requests(vocab, list(want), seed=7))
    assert report["completed"] == len(want)
    every = [w for ws in want.values() for w in ws]
    assert report["prefill_width_dispatches"] == {256: 3, 512: 4}
    table = eng.serving._table()
    assert table[:, COL["prefill_rows_computed"]].sum() == sum(every)
    assert table[:, COL["prefill_dispatches"]].sum() == len(every)
    assert report["prefill_row_fill"] == round(sum(want) / sum(every), 4)
    eng.close()
    events = [e for e in json.load(open(trace_path))
              if e.get("name") in ("prefill", "prefill_chunk")]
    prefills = [e for e in events if e["name"] == "prefill"]
    assert len(prefills) == len(want)
    for pf in prefills:
        rows = [c["args"]["rows"] for c in events
                if c["name"] == "prefill_chunk"
                and pf["ts"] <= c["ts"] < pf["ts"] + pf["dur"]]
        args = pf["args"]
        assert rows == want[args["prompt_tokens"]]
        assert args["rows_computed"] == sum(rows)
        assert args["chunks"] == len(rows)


# --------------------------------------------------------------------- #
# (f) A second start loads the narrow width: no trace, no lowering
# --------------------------------------------------------------------- #
@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    # (the directory alone: JAX's own persistent cache stays off, as in
    # every test: XLA:CPU's reloaded executables are not dependable)
    monkeypatch.setattr(engine_mod, "_programs_dir",
                        lambda: str(tmp_path / "prefill_widths"))
    return tmp_path / "prefill_widths"


def _start(cfg, params, extra, prompt, **inference):
    """(widths lowered by this start, what the prompt gives)."""
    eng = _engine(cfg, params, extra, **inference)
    programs = eng._prefill_fn
    lower, lowered = programs.jitted.lower, []

    class Counted:
        def lower(self, *args):
            lowered.append(args[programs.width_arg].shape[1])
            return lower(*args)
    programs.jitted = Counted()
    got = _through(eng, prompt)
    eng.close()
    return lowered, got


def test_a_second_start_loads_the_narrow_width(compile_cache):
    cfg, params, vocab, extra = _latent()
    prompt = _tokens(3, 200, vocab)                  # 256 rows wide
    lowered, first = _start(cfg, params, extra, prompt)
    assert lowered == [512, 256]
    (left,) = compile_cache.iterdir()
    assert left.name.endswith("-256")
    lowered, second = _start(cfg, params, extra, prompt)
    assert lowered == [512]                          # 256: loaded
    assert second[0] == first[0]
    np.testing.assert_array_equal(second[1], first[1])
    np.testing.assert_array_equal(second[2], first[2])
    assert [f.name for f in compile_cache.iterdir()] == [left.name]

    # Another program (here: a pool of other shape) is another file ...
    lowered, _ = _start(cfg, params, extra, prompt, num_blocks=192)
    assert lowered == [512, 256]
    assert len(list(compile_cache.iterdir())) == 2
    # ... and a file that does not load is built again, and replaced.
    left.write_bytes(b"not an executable")
    lowered, again = _start(cfg, params, extra, prompt)
    assert lowered == [512, 256] and again[0] == first[0]
    lowered, _ = _start(cfg, params, extra, prompt)
    assert lowered == [512]


def test_executables_are_kept_only_beside_a_compile_cache(tmp_path,
                                                         monkeypatch):
    monkeypatch.undo()                       # the engine's own rule
    before = jax.config.jax_compilation_cache_dir
    try:                                     # (nothing compiles in here)
        jax.config.update("jax_compilation_cache_dir", None)
        assert engine_mod._programs_dir() is None
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert engine_mod._programs_dir() \
            == str(tmp_path / "prefill_widths")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
