"""A decode step's short-filter rows rewritten in place (PR 53):
``ops.filter_rows.shift_rows`` (in interpret mode) against the plain lines of
``inference.served.filter_rows``, which stay the prefill path, the CPU path
and the reference: bit for bit on the rows a live stream's filter gets and on
every page of the pool, over the three per-stream families' tiles; and the
rule that picks the path from what a program hands over.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deepspeed_tpu.inference import InferenceEngine, served     # noqa: E402
from deepspeed_tpu.ops import filter_rows as in_place           # noqa: E402
from deepspeed_tpu.parallel.topology import build_mesh          # noqa: E402

G, SG, LAYERS, PAGES = 2, 4, 3, 6
# A stream a slot, two groups: one without a page, one whose row is dead (a
# page but nothing live), two at position 0 (they start from zeros whatever
# the page holds), the rest carried.
PAGE = np.array([3, -1, 0, 5, 2, 4, -1, 1], np.int32)
POS = np.array([5, 7, 0, 9, 1, 0, 0, 3], np.int32)
LIVE = np.array([1, 0, 1, 0, 1, 1, 0, 1], bool)


def operands(tile, held, dtype, seed=0, K=1):
    """(pool, new rows, the program's ``StreamPages``) for a pool of
    ``tile`` a page."""
    rng = np.random.default_rng(seed)
    C = int(np.prod(tile)) // held
    pool = jnp.asarray(rng.standard_normal((LAYERS, G, PAGES) + tile), dtype)
    new = jnp.asarray(rng.standard_normal((G * SG, K, C)), dtype)
    live = np.zeros((G * SG, K), bool)
    live[:, 0] = LIVE
    sp = served.stream_pages(
        jnp.asarray(PAGE), jnp.asarray(POS[:, None] + np.arange(K)[None]),
        jnp.asarray(live), PAGES, SG, held)
    return pool, new, sp


def both(tile, held, dtype, layer, mesh=None, **kw):
    """((rows, pool) of the plain lines, of the program with its kernels on,
    whether that program holds the kernel, which streams wrote)."""
    pool, new, sp = operands(tile, held, dtype, **kw)
    plain = jax.jit(lambda p, n: served.filter_rows(sp, p, layer, n))
    kernel = jax.jit(lambda p, n: served.filter_rows(
        sp, p, layer, n, paged_kernel=True, mesh=mesh))
    took = "_filter_rows_kernel" in str(jax.make_jaxpr(kernel)(pool, new))
    return plain(pool, new), kernel(pool, new), took, np.asarray(sp.wrote)


def served_both_ways(monkeypatch, model, weights, num_blocks, prompt, pools):
    """A prompt and three decode iterations on an engine of ``model`` with
    its kernels on (interpret mode), as traced and again with the shape rule
    answering no: two (the ``decode`` span's ``filter_rows_in_place``, the
    tokens, the iterations' logits, the stream's page of each of ``pools``).
    The family's serving tests call this at a size whose tile the kernel
    takes."""
    def stream(takes):
        with monkeypatch.context() as m:
            if not takes:
                m.setattr(in_place, "takes", lambda *a: False)
            eng = InferenceEngine(
                model, weights, config={"inference": dict(
                    max_slots=4, max_seq_len=128, block_size=4,
                    prefill_chunk=8, paged_kernel=True,
                    num_blocks=num_blocks)},
                mesh=build_mesh(devices=jax.devices()[:1]))
            slot = eng.select_slot(prompt, 4)
            tok, _ = eng.prefill(prompt, slot, return_logits=True,
                                 max_new_tokens=4)
            eng.activate_slot(slot, len(prompt), tok)
            toks, logits = [tok], []
            for _ in range(3):
                sampled, lg = eng.decode_once(return_logits=True)
                toks.append(int(sampled[slot]))
                logits.append(np.asarray(lg[slot]))
        page = int(eng.block_tables[slot][-1])
        return (eng.filter_rows_in_place, toks, np.stack(logits),
                *(np.asarray(eng.cache[name])[:, 0, page] for name in pools))
    return stream(True), stream(False)


def assert_the_same_stream(kernel, plain):
    """The kernel took the rows, the plain lines did not, and everything the
    stream produced and left behind is equal bit for bit."""
    assert (kernel[0], plain[0]) == (1, 0)
    assert kernel[1] == plain[1]
    for got, want in zip(kernel[2:], plain[2:]):
        np.testing.assert_array_equal(got, want)
    rows = kernel[3]                                   # (the rows landed)
    assert np.abs(rows.reshape(rows.shape[0], -1)).max(axis=1).min() > 0


def bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("tile, held, dtype, takes", [
    ((1, 288, 128), 3, jnp.bfloat16, True),     # kimi-linear: 96 rows a row
    ((1, 32, 128), 2, jnp.bfloat16, True),      # lfm2: 16
    ((1, 120, 128), 3, jnp.bfloat16, False),    # falcon-h1: 40, 2.5 tiles
    ((1, 24, 128), 3, jnp.float32, True),       # fp32: 8 rows are a tile
    ((1, 24, 128), 1, jnp.float32, True),       # two taps: nothing shifts
])
@pytest.mark.parametrize("layer", [0, 2])
def test_rows_and_pages_are_the_plain_lines_bit_for_bit(tile, held, dtype,
                                                        takes, layer):
    (rows0, pool0), (rows1, pool1), took, wrote = both(tile, held, dtype,
                                                       layer)
    assert took == takes == in_place.takes(
        (LAYERS, G, PAGES) + tile, dtype, held, dtype)
    assert wrote.tolist() == [True, False, True, False, True, True, False,
                              True]
    np.testing.assert_array_equal(bits(pool1), bits(pool0))
    np.testing.assert_array_equal(bits(rows1)[wrote], bits(rows0)[wrote])
    # every other layer and every page no stream owns is as it was
    before = operands(tile, held, dtype)[0]
    untouched = np.ones((LAYERS, G, PAGES), bool)
    for s in np.flatnonzero(wrote):
        untouched[layer, s // SG, PAGE[s]] = False
    np.testing.assert_array_equal(bits(pool1)[untouched],
                                  bits(before)[untouched])
    if took:
        # a stream that wrote nothing read nothing: zeros, then its row
        dead = np.asarray(rows1.astype(jnp.float32))[~wrote]
        new = np.asarray(operands(tile, held, dtype)[1].astype(jnp.float32))
        assert not dead[:, :held].any()
        np.testing.assert_array_equal(dead[:, held], new[~wrote][:, 0])


def test_a_stream_at_position_zero_starts_from_zeros_and_writes_its_page():
    (_, _), (rows, pool), took, _ = both((1, 32, 128), 2, jnp.bfloat16, 1)
    assert took
    new = operands((1, 32, 128), 2, jnp.bfloat16)[1]
    for s in (2, 5):                          # position 0, a page, live
        got = np.asarray(rows[s].astype(jnp.float32))
        assert not got[:2].any()
        page = np.asarray(pool[1, s // SG, PAGE[s]].astype(jnp.float32))
        page = page.reshape(2, -1)
        assert not page[0].any()
        np.testing.assert_array_equal(
            page[1], np.asarray(new[s, 0].astype(jnp.float32)))


@pytest.mark.parametrize("why, tile, held, dtype, kw", [
    ("a tile outside the 128-lane form", (1, 3, 96), 3, jnp.bfloat16, {}),
    ("several new rows a stream (a prefill chunk)", (1, 32, 128), 2,
     jnp.bfloat16, {"K": 4}),
])
def test_what_the_kernel_cannot_take_keeps_the_plain_lines(why, tile, held,
                                                           dtype, kw):
    (rows0, pool0), (rows1, pool1), took, _ = both(tile, held, dtype, 1,
                                                   **kw)
    assert not took, why
    np.testing.assert_array_equal(bits(pool1), bits(pool0))
    np.testing.assert_array_equal(bits(rows1), bits(rows0))


def test_a_program_that_freezes_a_snapshot_keeps_the_plain_lines():
    pool, new, _ = operands((1, 32, 128), 2, jnp.bfloat16)
    live = jnp.asarray(LIVE[:, None])
    sp = served.stream_pages(
        jnp.asarray(PAGE), jnp.asarray(POS[:, None]), live, PAGES, SG, 2,
        freeze=(jnp.zeros(G * SG, jnp.int32),
                jnp.full(G * SG, -1, jnp.int32)))
    text = str(jax.make_jaxpr(lambda p, n: served.filter_rows(
        sp, p, 0, n, paged_kernel=True))(pool, new))
    assert "_filter_rows_kernel" not in text


def test_new_rows_of_another_dtype_keep_the_plain_lines():
    pool, new, sp = operands((1, 32, 128), 2, jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda p, n: served.filter_rows(
        sp, p, 0, n, paged_kernel=True))(pool, new.astype(jnp.float32)))
    assert "_filter_rows_kernel" not in text


def test_a_step_takes_as_many_streams_as_divide_them_under_its_bytes():
    # kimi-linear: 256 pages of 72 KiB; lfm2: every page in one step
    assert in_place.streams_a_step(256, 288 * 128 * 2) == 32
    assert in_place.streams_a_step(128, 32 * 128 * 2) == 128
    assert in_place.streams_a_step(7, 2 ** 22) == 1
    assert in_place.streams_a_step(6, 2 ** 21) == 2


def test_each_shard_of_a_dp_mesh_rewrites_its_own_groups():
    mesh = build_mesh(devices=jax.devices()[:2])
    (rows0, pool0), (rows1, pool1), took, wrote = both(
        (1, 32, 128), 2, jnp.bfloat16, 1, mesh=mesh)
    assert took
    np.testing.assert_array_equal(bits(pool1), bits(pool0))
    np.testing.assert_array_equal(bits(rows1)[wrote], bits(rows0)[wrote])
