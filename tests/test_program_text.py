"""A served family writes its block, not its programs (ISSUE 56): the
engine's three programs are ``ServedModel``'s, written once over a family's
``embed`` / ``forward`` / ``head`` and the rows ``served.Rows`` hands them.

1. **The programs are what they were.**  For each of the nine fixtures of
   ``tests/decode_step_hlo.py`` — ONE engine a fixture and kernel arm,
   built at its first case and read by all the others (kernels off here;
   on, in interpret mode: ``test_program_text_kernels.py``, a file of its
   own so that the suite's workers share the two halves) — a case a program
   kind holds

   - what the engine computes on the CPU (first token and logits of a
     three-chunk prompt, two decode iterations' tokens and logits) to the
     bytes the tree at PR 55 computed, hashed: no exception;
   - each program's lowered text to ``tests/data/program_text_pr55.json``,
     written on that tree before the programs moved: the same text, or the
     same operations in another order (equal ORDER-FREE hashes) — or, for
     the programs ``MOVED`` names with their causes, the text this PR left
     (``tests/data/program_text_pr56.json``, the same file written on this
     PR's tree, so that the next change to them shows), and for the programs
     ``MOVED_BY_PR58`` names — the kernel arm of the six expert-layer
     families, whose grouped product reads its rows through the plan — the
     text PR 58 left (``program_text_pr58.json``); the family PR 59 added
     is held to its PR's file (``program_text_pr59.json``: what it computes,
     always) and, for the two programs ``MOVED_BY_PR60`` names — the block
     selection's order found by a threshold, not by two sorts — to the text
     PR 60 left (``program_text_pr60.json``), and since PR 63 — a decode
     step's selection reads a shared block's pooled keys once, and both
     programs count the blocks their selection gathered — to the text PR 63
     left (``program_text_pr63.json``, ``MOVED_BY_PR63``); PR 65's fixture
     (a chunk whose runs are dense: the attend's chunk-shaped body) to what
     its PR left and, but for ``MOVED_BY_PR65``, to what the parent of PR 65
     lowered for it (``program_text_pr65.json``); and since PR 67 — the
     K/V write takes a RUN of a stream's rows a grid step — every program
     ``MOVED_BY_PR67`` names (the programs that land MORE than one row a
     stream in K/V pages, whatever their family and kernel arm) to the text
     PR 67 left (``program_text_pr67.json``: every fixture in one file,
     ``python tests/decode_step_hlo.py OUT.json DIR ALL``).  A program that
     lowers to none of them fails: run ``python tests/decode_step_hlo.py OUT.json
     DIR`` on both trees and ``diff`` the blanked texts to see which lines
     moved;
   - ``verify`` of a family whose cache is a state a stream to the ONE
     refusal.

2. **``Rows``' two constructors against plain NumPy**: positions, dead
   slots and groups, padding past ``last_idx``, the ``freeze`` pair, the
   last-row pick over trailing axes.
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import decode_step_hlo as harness                               # noqa: E402
from deepspeed_tpu.inference.kv_cache import DEAD_BLOCK         # noqa: E402
from deepspeed_tpu.inference.served import Rows, served_model   # noqa: E402

DATA = os.path.join(harness.TESTS, "data")
GOLDEN = json.load(open(os.path.join(DATA, "program_text_pr55.json")))
LEFT_BY_PR56 = json.load(open(os.path.join(DATA, "program_text_pr56.json")))
LEFT_BY_PR58 = json.load(open(os.path.join(DATA, "program_text_pr58.json")))
# The families later PRs ADDED (``harness.ADDED``), each by the tree of its
# own PR: ``python tests/decode_step_hlo.py OUT.json DIR ADDED``.
ADDED_BY_PR59 = json.load(open(os.path.join(DATA, "program_text_pr59.json")))
LEFT_BY_PR60 = json.load(open(os.path.join(DATA, "program_text_pr60.json")))
LEFT_BY_PR63 = json.load(open(os.path.join(DATA, "program_text_pr63.json")))
# ... and the family PR 62 added (``harness.ADDED_BY_PR62``: ``python
# tests/decode_step_hlo.py OUT.json DIR PR62``).  PR 62 gave ``Rows`` each
# row's last attendable position as a field of its own: for every family
# above it IS ``positions``, the same array, so their programs are text for
# text what they were and no ``MOVED_*`` gained an entry.
ADDED_BY_PR62 = json.load(open(os.path.join(DATA, "program_text_pr62.json")))
# ... and the family PR 64 added (``harness.ADDED_BY_PR64``: ``... PR64``).
# PR 64 lifted the KDA layers' page bookkeeping out of the kimi_linear
# family's ``forward`` (``inference/kda_state.py``) and put afmoe's output
# gate behind ``kv_pages.output_gate``: both families' programs are the same
# operations and no ``MOVED_*`` gained an entry.
ADDED_BY_PR64 = json.load(open(os.path.join(DATA, "program_text_pr64.json")))
# PR 65 gave the attend of a step of ``_DENSE_ROWS`` query rows a K/V head or
# more a body of its own.  No fixture above has such a step (chunks of 8 rows
# under at most seven query heads a K/V head, and a head_dim of 16 folds):
# every program of theirs, both arms, is text for text the parent's and no
# ``MOVED_*`` gained an entry.  ``harness.ADDED_BY_PR65`` is the fixture that
# HAS one (``python tests/decode_step_hlo.py OUT.json DIR PR65``):
# ``program_text_pr65.json`` holds what the PARENT of PR 65 lowered for it
# (``was``: the same harness file over ``git archive`` of 7e6290f) beside what
# PR 65 left (``left``).
PR65 = json.load(open(os.path.join(DATA, "program_text_pr65.json")))
CHUNK_BODY = ("a run of a prefill chunk that is `_DENSE_ROWS` query rows a K/V "
              "head or more, over a pool that does not fold, takes the "
              "attend's chunk-shaped body: `_pattn_chunk_kernel` in place of "
              "`_pattn_kernel` in both classes' attends (bf16 operands as "
              "stored, lane-wide `m` / `l`, groups of `_CHUNK_KEYS` keys, two "
              "row bands, the mask only where a row's edge lies) and one "
              "more scalar-prefetched operand, the streams' mask edges")
# ... kernels ON only; ``decode_step`` (16 rows a K/V head) and
# ``verify_step`` (48) keep ``_pattn_kernel``.
MOVED_BY_PR65 = {"afmoe_dense": {"prefill_step": (CHUNK_BODY,)}}
# PR 67, BOTH arms (the in-place write is the one write of both since PR 29:
# ``inference.paged_kernel`` picks the attend): every program whose streams
# bring MORE than one row to K/V pages — a ``prefill_step`` (the chunk's
# width), a ``verify_step`` (``spec_k + 1``) and the block ``decode_step`` of
# a model of blocks.  Every ``decode_step`` of a model of tokens (K = 1: the
# row grid and body it had), every program of the latent and retention
# families (``_latent_write_kernel`` / no pages) keeps its text.
LEFT_BY_PR67 = json.load(open(os.path.join(DATA, "program_text_pr67.json")))
RUNS = ("`paged_write` is told the rows a stream brings (`stream_rows` = K, "
        "consecutive positions) and takes a grid step a RUN — a stream's "
        "rows in one page — not a row: `(K - 1) // block_size + 2` steps a "
        "stream (one where `Rows.one_block`: a model of blocks' block in a "
        "page); the runs' first row and count are two more scalar-"
        "prefetched operands, a stream's rows ride one fp32 block "
        "`[nH, K, f*D]` (heads major), and the kernel's body stores a run "
        "that IS a page whole and loops over the rows of any other, "
        "selecting each into the 16 tile rows that hold its offset")
MOVED_BY_PR67 = {
    family: {kind: (RUNS,) for kind in kinds}
    for family, kinds in {
        "gpt2": ("prefill_step", "verify_step"),
        "afmoe": ("prefill_step", "verify_step"),
        "smallthinker": ("prefill_step", "verify_step"),
        "lfm2": ("prefill_step",), "falcon_h1": ("prefill_step",),
        "minicpm_sala": ("prefill_step",),
        "sdar": ("decode_step", "prefill_step"),
        "solar_open2": ("prefill_step",),
        "afmoe_dense": ("prefill_step", "verify_step"),
    }.items()}
# The families added one a PR, each held to its own PR's file.
ADDED_LATER = [(family, harness.ADDED_BY_PR62, ADDED_BY_PR62, 62)
               for family in sorted(harness.ADDED_BY_PR62)] \
    + [(family, harness.ADDED_BY_PR64, ADDED_BY_PR64, 64)
       for family in sorted(harness.ADDED_BY_PR64)]

# Why a program's operations are not, line for line, the ones PR 55 lowered
# (CHANGES.md, PR 56, quotes the lines).  Where the seven copies of the
# programs differed the one copy picks a side — the one more cells ran:
LIVE = ("the one rule for `live`: a slot is live where ANY column of its "
        "table row holds a block (the latent family asked its first column)")
POS = ("positions reach `embed` through `Rows`: grouped, then shaped like "
       "their tokens again — one reshape, and GPT-2 alone reads them")
K1 = ("`decode` is the K = 1 `verify`: `lengths + arange(1)`, the head over "
      "`[S, 1, H]` and then `[:, 0]` (this family sliced first)")
COLS = ("a chunk's columns are `arange(width)` for the positions and an "
        "iota each for `live` and the last-row pick (this family shared one)")
READS = ("the family's two closures read the program's `Rows` (the table, "
         "dead for an inactive group; the positions), not the raw operands")
STATE = {"decode_step": (K1,), "prefill_step": (COLS,)}
MOVED = {
    "gpt2": {kind: (POS,) for kind in
             ("decode_step", "prefill_step", "verify_step")},
    "latent_share": {"decode_step": (LIVE,), "verify_step": (LIVE,)},
    "latent_hyper": {"decode_step": (LIVE,), "verify_step": (LIVE,)},
    "lfm2": STATE, "falcon_h1": STATE, "kimi_linear": STATE,
    "retention": {"decode_step": (K1, READS),
                  "prefill_step": (COLS, READS)},
}
# PR 58, kernels ON only: every program of a family with an expert layer
# (``verify_step`` where the family has one).
ROWS = ("the grouped product takes the tokens `x [T, H]` and the plan's "
        "`src` and brings each tile's rows in itself: the `[M, H]` gather "
        "`x[src]` in front of the kernel is gone, `tile_rows` (the rows each "
        "tile holds) is new, and the kernel's body widens `x` to float32 "
        "once and has the row loop")
MOVED_BY_PR58 = {
    family: {kind: (ROWS,) for kind in kinds}
    for family, kinds in {
        "afmoe": ("decode_step", "prefill_step", "verify_step"),
        "latent_share": ("decode_step", "prefill_step", "verify_step"),
        "latent_hyper": ("decode_step", "prefill_step", "verify_step"),
        "smallthinker": ("decode_step", "prefill_step", "verify_step"),
        "lfm2": ("decode_step", "prefill_step"),
        "kimi_linear": ("decode_step", "prefill_step"),
    }.items()}
# PR 60, both arms: the two programs of the family that selects blocks.
THRESHOLD = ("`sparse_select.choose` finds the `topk` largest block scores by "
             "a threshold (32 steps of bisection over order-preserving int32 "
             "keys, one loop) and writes the table's entries there in "
             "ascending order by counting (a product with a triangular 0/1 "
             "matrix, a compare-and-sum): the `top_k`, the `sort` of its "
             "indices and the gather through the table are gone, and the "
             "order is found once a program, outside the `lax.map`s that "
             "batch the scores")
MOVED_BY_PR60 = {"minicpm_sala": {"decode_step": (THRESHOLD,),
                                  "prefill_step": (THRESHOLD,)}}
# PR 63, both arms: the same two programs.
SHARED = ("`sparse_select.select_blocks_counted` with one row a stream reads "
          "its tables first (`_shared_plan`: compares over `[S, S]` and "
          "`[S, W]`), deals the streams that share leading blocks into tiles "
          "and, under a `cond`, in a loop of as many steps as there are "
          "tiles, gathers a tile's group's pooled keys ONCE and contracts "
          "them with all the tile's query rows in one product "
          "(`_tile_scores`, a stream's own slots past the prefix beside it, "
          "one softmax over both); the per-stream gather of every table's "
          "width is the `cond`'s other arm")
COUNTER = ("the counter `ck_blocks_read` (blocks of pooled keys the selection "
           "gathered) rides the fetch: the row of counters is one int32 "
           "wider, and a chunk program adds its constant a sparse layer — "
           "nothing else of `prefill_step` moved (the `K > 1` arm of the "
           "selection is PR 60's line for line)")
MOVED_BY_PR63 = {"minicpm_sala": {"decode_step": (SHARED, COUNTER),
                                  "prefill_step": (COUNTER,)}}
KINDS = ("decode_step", "prefill_step", "verify_step", "outputs")
REFUSES = ("retention", "lfm2", "falcon_h1", "kimi_linear")


def held_to_the_golden(family, kind, arm):
    got, want = harness.golden(family, arm), GOLDEN[family][arm]
    if kind == "outputs":
        assert got["outputs"] == want["outputs"], (
            f"{family}, kernels {arm}: the engine computes other bits than "
            "the tree at PR 55 did")
        return
    names = sorted(n for n in want["programs"] if n.startswith(kind))
    assert names == sorted(n for n in got["programs"] if n.startswith(kind))
    if kind == "verify_step" and family in REFUSES:
        assert not names
        with pytest.raises(NotImplementedError,
                           match="rolled back.*spec_k"):
            served_model(harness.FAMILIES[family]()[0]).verify(
                None, None, None, None, None, num_groups=1,
                paged_kernel=False)
        return
    assert names
    by_pr58 = MOVED_BY_PR58.get(family, {}) if arm == "on" else {}
    for name in names:
        g, w = got["programs"][name], want["programs"][name]
        if kind in MOVED_BY_PR67.get(family, {}):
            assert g["order_free"] == LEFT_BY_PR67[family][arm]["programs"][
                    name]["order_free"], (
                f"{family}.{arm}.{name}: other operations than PR 67 left "
                f"(moved then by: {'; '.join(MOVED_BY_PR67[family][kind])})")
            continue
        if kind in by_pr58:
            assert g["order_free"] == LEFT_BY_PR58[family][arm]["programs"][
                    name]["order_free"], (
                f"{family}.{arm}.{name}: other operations than PR 58 left "
                f"(moved then by: {'; '.join(by_pr58[kind])})")
            continue
        if g["order_free"] == w["order_free"]:
            continue            # the same lines (in another order at most)
        assert kind in MOVED.get(family, {}), (
            f"{family}.{arm}.{name}: other operations than at PR 55, and "
            "no cause on record")
        assert g["order_free"] == LEFT_BY_PR56[family][arm]["programs"][
                name]["order_free"], (
            f"{family}.{arm}.{name}: other operations than PR 56 left "
            f"(moved then by: {'; '.join(MOVED[family][kind])})")


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("family", sorted(harness.FAMILIES))
def test_the_programs_are_what_they_were(family, kind):
    held_to_the_golden(family, kind, "off")


@pytest.mark.parametrize("arm", sorted(harness.ARMS))
@pytest.mark.parametrize("kind", ["decode_step", "prefill_step", "outputs",
                                  "verify_step"])
@pytest.mark.parametrize("family", sorted(harness.ADDED))
def test_an_added_familys_programs_are_what_its_pr_left(family, kind, arm):
    """PR 59's family (sparse layers beside Lightning layers): the bits its
    PR computed, and the text its PR lowered — or, for the programs
    ``MOVED_BY_PR60`` names with their cause, the text PR 60 left
    (``program_text_pr60.json``: ``python tests/decode_step_hlo.py OUT.json
    DIR ADDED`` on that tree), or, for those ``MOVED_BY_PR63`` names, the
    text PR 63 left (``program_text_pr63.json``, written the same way);
    ``verify`` refuses (a state a stream)."""
    got, want = harness.golden(family, arm), ADDED_BY_PR59[family][arm]
    if kind == "outputs":
        assert got["outputs"] == want["outputs"]
        return
    names = sorted(n for n in want["programs"] if n.startswith(kind))
    assert names == sorted(n for n in got["programs"] if n.startswith(kind))
    if kind == "verify_step":
        assert not names
        with pytest.raises(NotImplementedError,
                           match="rolled back.*spec_k"):
            served_model(harness.ADDED[family]()[0]).verify(
                None, None, None, None, None, num_groups=1,
                paged_kernel=False)
        return
    assert names
    left_by = "its PR"
    for pr, moved, left in ((67, MOVED_BY_PR67, LEFT_BY_PR67),
                            (63, MOVED_BY_PR63, LEFT_BY_PR63),
                            (60, MOVED_BY_PR60, LEFT_BY_PR60)):
        if kind in moved.get(family, {}):
            want = left[family][arm]
            left_by = "PR {} (moved then by: {})".format(
                pr, "; ".join(moved[family][kind]))
            break
    for name in names:
        assert got["programs"][name]["order_free"] \
            == want["programs"][name]["order_free"], (
            f"{family}.{arm}.{name}: other operations than {left_by} left")


@pytest.mark.parametrize("arm", sorted(harness.ARMS))
@pytest.mark.parametrize("kind", ["decode_step", "prefill_step", "outputs",
                                  "verify_step"])
@pytest.mark.parametrize("family,made_by,golden,pr", ADDED_LATER,
                         ids=[a[0] for a in ADDED_LATER])
def test_a_family_added_later_is_what_its_pr_left(family, made_by, golden,
                                                  pr, kind, arm):
    """PR 62's family (generation by diffusion over blocks): the bits its PR
    computed — two prefill chunks under the block mask, then four passes
    over the stream's block — and the text its PR lowered; ``verify``
    refuses (nothing is drafted in a model of blocks).  PR 64's (K/V pages
    under an output gate beside delta-rule states): the same, by the common
    scenario; ``verify`` refuses (a state a stream)."""
    got, want = harness.golden(family, arm), golden[family][arm]
    if kind == "outputs":
        assert got["outputs"] == want["outputs"]
        return
    names = sorted(n for n in want["programs"] if n.startswith(kind))
    assert names == sorted(n for n in got["programs"] if n.startswith(kind))
    if kind == "verify_step":
        assert not names
        with pytest.raises(NotImplementedError,
                           match="rolled back.*spec_k"):
            served_model(made_by[family]()[0]).verify(
                None, None, None, None, None, num_groups=1,
                paged_kernel=False)
        return
    assert names
    if kind in MOVED_BY_PR67.get(family, {}):
        want, pr = LEFT_BY_PR67[family][arm], "67 (moved then by: {})".format(
            "; ".join(MOVED_BY_PR67[family][kind]))
    for name in names:
        assert got["programs"][name]["order_free"] \
            == want["programs"][name]["order_free"], (
            f"{family}.{arm}.{name}: other operations than PR {pr} left")


@pytest.mark.parametrize("arm", sorted(harness.ARMS))
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("family", sorted(harness.ADDED_BY_PR65))
def test_a_dense_chunk_alone_takes_the_chunk_body(family, kind, arm):
    """PR 65's fixture (one K/V head of 128 under sixteen query heads: a
    chunk of 8 positions is 128 query rows a K/V head): every program is the
    text PR 65 left, and that is the text the PARENT lowered for the same
    fixture except where ``MOVED_BY_PR65`` names the program with its cause
    — the kernel arm's ``prefill_step`` and nothing else; what the fixture
    computes is, to the byte, what the parent computed in both arms."""
    got = harness.golden(family, arm)
    was, left = PR65["was"][family][arm], PR65["left"][family][arm]
    if kind == "outputs":
        assert got["outputs"] == left["outputs"] == was["outputs"]
        return
    names = sorted(n for n in left["programs"] if n.startswith(kind))
    assert names and names == sorted(n for n in got["programs"]
                                     if n.startswith(kind))
    assert names == sorted(n for n in was["programs"] if n.startswith(kind))
    moved = arm == "on" and kind in MOVED_BY_PR65[family]
    by_pr67 = kind in MOVED_BY_PR67[family]
    for name in names:
        text = got["programs"][name]["order_free"]
        if by_pr67:     # (PR 67 moved it in both arms: held to what IT left)
            assert text == LEFT_BY_PR67[family][arm]["programs"][name][
                "order_free"], (
                f"{family}.{arm}.{name}: other operations than PR 67 left")
            continue
        assert text == left["programs"][name]["order_free"], (
            f"{family}.{arm}.{name}: other operations than PR 65 left")
        assert (text != was["programs"][name]["order_free"]) == moved, (
            f"{family}.{arm}.{name}: "
            + ("the parent's text, where the chunk body was to move it"
               if moved else "moved, and no cause on record"))


@pytest.mark.parametrize("families,was,now,moved_by", [
    (harness.FAMILIES, GOLDEN, LEFT_BY_PR56,
     lambda family, arm: MOVED.get(family, {})),
    (harness.FAMILIES, LEFT_BY_PR56, LEFT_BY_PR58, lambda family, arm:
     MOVED_BY_PR58.get(family, {}) if arm == "on" else {}),
    (harness.ADDED, ADDED_BY_PR59, LEFT_BY_PR60,
     lambda family, arm: MOVED_BY_PR60.get(family, {})),
    (harness.ADDED, LEFT_BY_PR60, LEFT_BY_PR63,
     lambda family, arm: MOVED_BY_PR63.get(family, {})),
    ({**harness.FAMILIES, **harness.ADDED, **harness.ADDED_BY_PR62,
      **harness.ADDED_BY_PR64, **harness.ADDED_BY_PR65},
     {**LEFT_BY_PR58, **LEFT_BY_PR63, **ADDED_BY_PR62, **ADDED_BY_PR64,
      **PR65["left"]}, LEFT_BY_PR67,
     lambda family, arm: MOVED_BY_PR67.get(family, {}))],
    ids=["pr56", "pr58", "pr60", "pr63", "pr67"])
def test_the_causes_on_record_are_of_the_programs_that_moved(families, was,
                                                             now, moved_by):
    """``MOVED`` names the programs whose operations PR 56 left other than
    PR 55's, ``MOVED_BY_PR58`` those PR 58 left other than PR 56's (the
    kernel arm alone), ``MOVED_BY_PR60`` those PR 60 left other than PR
    59's (the added family's), ``MOVED_BY_PR63`` those PR 63 left other
    than PR 60's, ``MOVED_BY_PR67`` those PR 67 left other than its parent's
    (every fixture's, both arms), and no other (an entry would outlive its
    cause); what every fixture computes moved in none."""
    for family in families:
        for arm in harness.ARMS:
            old, new = was[family][arm], now[family][arm]
            assert old["outputs"] == new["outputs"]
            assert sorted(old["programs"]) == sorted(new["programs"])
            moved = {name.split("_step")[0] + "_step"
                     for name, hashes in new["programs"].items()
                     if hashes["order_free"]
                     != old["programs"][name]["order_free"]}
            assert moved == set(moved_by(family, arm)), (family, arm)


# --------------------------------------------------------------------- #
# 2. The two constructors
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("K,groups", [(1, 1), (1, 2), (3, 2)])
def test_rows_of_slots_against_numpy(K, groups):
    S, W = 4, 5
    lengths = np.asarray([7, 0, 12, 3], np.int32)
    tables = np.full((S, W), DEAD_BLOCK, np.int32)
    tables[0, :2] = (4, 9)
    tables[2, 3] = 1            # a block in a LATER column only: live
    tables[3, 0] = 6            # (slot 1 holds none: dead)
    rows = Rows.of_slots(jnp.asarray(lengths), jnp.asarray(tables), K,
                         groups, (3, 2))
    Sg = S // groups
    np.testing.assert_array_equal(rows.tables,
                                  tables.reshape(groups, Sg, W))
    want = lengths[:, None] + np.arange(K)[None]
    np.testing.assert_array_equal(rows.positions,
                                  want.reshape(groups, Sg, K))
    np.testing.assert_array_equal(
        rows.live, np.repeat([[True], [False], [True], [True]], K, axis=1))
    assert rows.widths == (3, 2) and not rows.chunked \
        and rows.freeze is None
    # a causal model's rows see as far as themselves: the SAME array
    assert rows.sees is rows.positions
    blocks = Rows.of_slots(jnp.asarray(lengths), jnp.asarray(tables), K,
                           groups, (3, 2), block_length=4)
    np.testing.assert_array_equal(
        blocks.sees, (want // 4 * 4 + 3).reshape(groups, Sg, K))
    np.testing.assert_array_equal(blocks.positions, rows.positions)


@pytest.mark.parametrize("freeze", [False, True])
def test_rows_of_a_chunk_against_numpy(freeze):
    G, C, W = 3, 8, 4
    bt = np.arange(G * W, dtype=np.int32).reshape(G, W)
    start = np.asarray([0, 16, 8], np.int32)
    last_idx = np.asarray([7, 2, 5], np.int32)
    active = np.asarray([1, 1, 0], np.int32)
    pair = (jnp.asarray([3, -1, -1], jnp.int32),
            jnp.asarray([5, DEAD_BLOCK, DEAD_BLOCK], jnp.int32))
    rows = Rows.of_chunk(jnp.asarray(bt), jnp.asarray(start),
                         jnp.asarray(last_idx), jnp.asarray(active), C,
                         (W,), pair if freeze else None)
    assert rows.tables.shape == (G, 1, W) and rows.chunked
    np.testing.assert_array_equal(rows.tables[:2, 0], bt[:2])
    assert (np.asarray(rows.tables[2]) == DEAD_BLOCK).all()   # inactive
    want = start[:, None] + np.arange(C)[None]
    np.testing.assert_array_equal(rows.positions, want[:, None, :])
    np.testing.assert_array_equal(
        rows.live, (active[:, None] > 0)
        & (np.arange(C)[None] <= last_idx[:, None]))
    assert not np.asarray(rows.live)[1, 3:].any()     # padding: not traffic
    assert not np.asarray(rows.live)[2].any()         # the inactive group
    assert (rows.freeze is pair) if freeze else (rows.freeze is None)
    assert rows.sees is rows.positions
    blocks = Rows.of_chunk(jnp.asarray(bt), jnp.asarray(start),
                           jnp.asarray(last_idx), jnp.asarray(active), C,
                           (W,), pair if freeze else None, block_length=4)
    # the end of a row's block, no further than the chunk's last live row
    np.testing.assert_array_equal(
        blocks.sees[:, 0], np.minimum(want // 4 * 4 + 3,
                                      (start + last_idx)[:, None]))


@pytest.mark.parametrize("trailing", [(6,), (4, 6)])
def test_the_last_row_pick_over_trailing_axes(trailing):
    G, C = 3, 8
    x = np.random.default_rng(0).normal(size=(G, C) + trailing).astype(
        np.float32)
    last_idx = np.asarray([7, 0, 4], np.int32)
    got = Rows.last(jnp.asarray(x), jnp.asarray(last_idx))
    assert got.shape == (G,) + trailing
    np.testing.assert_array_equal(got, x[np.arange(G), last_idx])
