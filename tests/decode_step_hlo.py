"""The serving engine's programs as TEXT and as bits, for a tiny engine of
every served family (not a test file: ``tests/test_program_text.py``
compares, and shares the readings taken here).

For each of the nine fixtures, with the Pallas kernels off and on (interpret
mode), ONE engine gives

- the lowered text of the engine's own programs — ``_build_decode_step``,
  ``_build_prefill_step`` at every width of ``engine.prefill_widths`` and,
  for a family whose cache can be rolled back over rejected drafts,
  ``_build_verify_step`` — as ``jax.jit(...).lower(...).as_text()``:
  StableHLO without locations, so a named scope or a moved line of Python
  changes nothing in it, and an operation added, dropped or reordered does.
  Two hashes a program: of the text as it is, and an ORDER-FREE one (SSA
  value names and functions' numeric suffixes blanked, the lines sorted) —
  equal order-free hashes and unequal exact ones mean the same operations
  traced in another order;
- the sha256 of the bytes of what it computes on the CPU: the first token
  and logits of a three-chunk prompt, then two decode iterations' tokens
  and logits.

``python tests/decode_step_hlo.py OUT.json [DIR]`` writes the golden JSON
(and every program's blanked text into DIR, a line an operation, unsorted,
for ``diff``); ``tests/data/program_text_pr55.json`` was written by running
this file in a ``git archive`` of the tree at PR 55 (e2db9ea), BEFORE the
refactor it guards touched ``inference/``.
"""
import dataclasses
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TESTS)
for path in (ROOT, TESTS):
    if path not in sys.path:
        sys.path.insert(0, path)

CHUNK = 8
VERIFY_ROWS = 3                     # K of the lowered ``verify_step``
ARMS = {"off": False, "on": True}   # the Pallas kernels (interpret mode)


# --------------------------------------------------------------------- #
# The nine fixtures: () -> (config, params, ``inference`` keys of its own)
# --------------------------------------------------------------------- #
def _gpt2():
    from deepspeed_tpu.models.gpt2 import GPT2_CONFIGS, gpt2_init
    cfg = dataclasses.replace(GPT2_CONFIGS["gpt2-tiny"], dtype=jnp.float32)
    return cfg, gpt2_init(jax.random.PRNGKey(0), cfg), {"block_size": 16}


def _retention():
    from deepspeed_tpu.models.brumby import BrumbyConfig, brumby_init
    cfg = BrumbyConfig(vocab_size=128, hidden_size=32, intermediate_size=64,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=16,
                       max_position_embeddings=128, dtype=jnp.float32)
    return cfg, brumby_init(jax.random.PRNGKey(0), cfg), {
        "block_size": 8, "num_blocks": 8}


def _latent(**kw):
    from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                                  deepseek_v3_init)
    cfg = DeepseekV3Config(**dict(dict(
        vocab_size=250, vocab_rows_held=256, hidden_size=64,
        intermediate_size=128, moe_intermediate_size=32,
        num_hidden_layers=3, first_k_dense_replace=1,
        num_attention_heads=4, n_routed_experts=16, held=(4, 8),
        num_experts_per_tok=4, n_group=4, topk_group=2, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, max_position_embeddings=256,
        rope_original_max_position_embeddings=32, rope_factor=8.0,
        dtype=jnp.float32, initializer_range=0.08), **kw))
    return cfg, deepseek_v3_init(jax.random.PRNGKey(0), cfg), {
        "block_size": 16}


def _hyper():
    return _latent(n_group=1, topk_group=1, hc_mult=4, held=(0, 16))


def _afmoe():
    from deepspeed_tpu.models.afmoe import AfmoeConfig, afmoe_init
    cfg = AfmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, sliding_window=8,
        max_position_embeddings=256, dtype=jnp.float32)
    return cfg, afmoe_init(jax.random.PRNGKey(0), cfg), {
        "num_blocks": {"full": 64, "window": 40}}


def _smallthinker():
    """``test_smallthinker_serving.tiny`` cut to five layers (``0 1 1 1 0``:
    two full layers, three of the window's): a layer costs a second of
    interpret-mode lowering a program here and adds no kind of line."""
    from deepspeed_tpu.models.smallthinker import (SmallthinkerConfig,
                                                   smallthinker_init)
    cfg = SmallthinkerConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=5,
        num_attention_heads=14, num_key_value_heads=2, head_dim=16,
        moe_ffn_hidden_size=32, moe_num_primary_experts=8,
        moe_num_active_primary_experts=3, sliding_window_size=8,
        max_position_embeddings=256, dtype=jnp.float32)
    return cfg, smallthinker_init(jax.random.PRNGKey(0), cfg), {
        "num_blocks": {"full": 64, "window": 40}}


def _lfm2():
    from deepspeed_tpu.models.lfm2 import CONV, FULL, Lfm2Config, lfm2_init
    cfg = Lfm2Config(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        num_experts_per_tok=2, layer_types=(CONV, FULL, CONV, CONV, CONV),
        max_position_embeddings=256, dtype=jnp.float32)
    return cfg, lfm2_init(jax.random.PRNGKey(0), cfg), {
        "num_blocks": {"full": 96, "conv": 16}}


def _falcon_h1():
    from deepspeed_tpu.models.falcon_h1 import (FalconH1Config,
                                                falcon_h1_init)
    cfg = FalconH1Config(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=10, num_key_value_heads=2,
        head_dim=16, mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
        mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4,
        mamba_chunk_size=8, max_position_embeddings=256, rope_theta=1e4,
        dtype=jnp.float32)
    return cfg, falcon_h1_init(jax.random.PRNGKey(0), cfg), {
        "num_blocks": {"full": 96, "state": 16}}


def _kimi_linear():
    from deepspeed_tpu.models.kimi_linear import (KimiLinearConfig,
                                                  kimi_linear_init)
    cfg = KimiLinearConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, kda_num_heads=2, kda_head_dim=16,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=8, held=(0, 4), num_experts_per_token=2,
        model_max_length=256, dtype=jnp.float32)
    return cfg, kimi_linear_init(jax.random.PRNGKey(0), cfg), {
        "num_blocks": {"latent": 96, "state": 16}}


# Every served family, by the benchmark's cell: GPT-2 (3), the latent family
# with a held share (4) and with several residual streams (7), retention
# (5), two classes of pages (6), a router ahead of its attention (11), pages
# beside a conv state (8), a state-space mixer beside attention in every
# layer (9), a delta-rule state beside a latent class (10).
FAMILIES = {"gpt2": _gpt2, "latent_share": _latent, "latent_hyper": _hyper,
            "retention": _retention, "afmoe": _afmoe,
            "smallthinker": _smallthinker, "lfm2": _lfm2,
            "falcon_h1": _falcon_h1, "kimi_linear": _kimi_linear}


def _minicpm_sala():
    """``test_minicpm_sala_serving.tiny`` at blocks of 8 (pooled keys of 4
    tokens every 2, top 3, dense up to 16 tokens): the scenario's prompt of
    19 tokens crosses ``dense_len`` in its third chunk of 8."""
    from deepspeed_tpu.models.minicpm_sala import (MinicpmSalaConfig,
                                                   minicpm_sala_init)
    cfg = MinicpmSalaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=4, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
        mixer_types=("minicpm4", "lightning-attn", "lightning-attn",
                     "minicpm4"),
        depth_scale_layers=8, dim_model_base=32, max_position_embeddings=256,
        sparse_kernel_size=4, sparse_kernel_stride=2, sparse_block_size=8,
        sparse_topk=2, sparse_window_size=8, sparse_init_blocks=1,
        sparse_dense_len=16, dtype=jnp.float32)
    return cfg, minicpm_sala_init(jax.random.PRNGKey(0), cfg), {
        "block_size": 8, "num_blocks": {"sparse": 64, "state": 16}}


# Families a later PR added, each held to the golden file of ITS PR
# (``tests/test_program_text.py``): PR 59's.
ADDED = {"minicpm_sala": _minicpm_sala}


def _sdar():
    """``test_sdar_serving.tiny``: blocks of 4 positions in pages of 8, two
    denoising steps under the static rule; the scenario's prompt of 19
    tokens is prefilled as far as 16 and its last 3 open the first block."""
    from deepspeed_tpu.models.sdar import SdarConfig, sdar_init
    cfg = SdarConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, max_position_embeddings=256,
        block_length=4, mask_token_id=127, denoising_steps=2,
        remasking="low_confidence_static", dtype=jnp.float32,
        initializer_range=0.3)
    return cfg, sdar_init(jax.random.PRNGKey(0), cfg), {
        "block_size": 8, "num_blocks": 64}


# PR 62's: a model generated in blocks (its ``decode_step`` is a pass over a
# block of every slot; ``served_bytes`` drives it by passes).
ADDED_BY_PR62 = {"sdar": _sdar}


def _solar_open2():
    """``test_solar_open2_serving.tiny`` at the harness's blocks of 4: one
    period G K K K, 4 : 2 attention heads under an output gate, a write
    strength up to 2."""
    from deepspeed_tpu.models.solar_open2 import (SolarOpen2Config,
                                                  solar_open2_init)
    cfg = SolarOpen2Config(
        vocab_size=128, hidden_size=64, moe_intermediate_size=32,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, kda_num_heads=4, kda_head_dim=16, n_routed_experts=16,
        held=(0, 4), num_experts_per_tok=2, max_position_embeddings=256,
        dtype=jnp.float32)
    return cfg, solar_open2_init(jax.random.PRNGKey(0), cfg), {
        "num_blocks": {"full": 96, "state": 16}}


# PR 64's: K/V pages under an output gate beside delta-rule states.
ADDED_BY_PR64 = {"solar_open2": _solar_open2}


def _afmoe_dense():
    """``_afmoe`` at ONE K/V head of 128 under sixteen query heads: a chunk
    of 8 positions is 128 query rows a K/V head and nothing folds
    (``ops.paged_attention._dense``), so the kernel arm's ``prefill_step``
    takes the attend's chunk-shaped body in both classes (the full table
    and the window's ring) while ``decode_step`` (16 rows) and
    ``verify_step`` (48) keep ``_pattn_kernel``."""
    from deepspeed_tpu.models.afmoe import AfmoeConfig, afmoe_init
    cfg = AfmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=16, num_key_value_heads=1, head_dim=128,
        num_experts=8, num_experts_per_tok=2, sliding_window=8,
        max_position_embeddings=256, dtype=jnp.float32)
    return cfg, afmoe_init(jax.random.PRNGKey(0), cfg), {
        "num_blocks": {"full": 64, "window": 40}}


# PR 65's: a chunk whose runs are dense (``tests/test_program_text.py``
# holds it to the text the PARENT of PR 65 lowered for it and to PR 65's).
ADDED_BY_PR65 = {"afmoe_dense": _afmoe_dense}


def engine(family: str, kernel: bool, dp: int = 1, **extra):
    """A tiny engine of ``family`` on ``dp`` host devices (``extra``: more
    top-level config blocks, ``telemetry``)."""
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg, params, inference = {**FAMILIES, **ADDED, **ADDED_BY_PR62,
                              **ADDED_BY_PR64, **ADDED_BY_PR65}[family]()
    conf = dict(max_slots=4, max_seq_len=128, block_size=4,
                prefill_chunk=CHUNK, paged_kernel=kernel)
    conf.update(inference)
    return InferenceEngine(cfg, params, config={"inference": conf, **extra},
                           mesh=build_mesh(devices=jax.devices()[:dp]))


# --------------------------------------------------------------------- #
# The programs' text
# --------------------------------------------------------------------- #
def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


_KEY = jax.ShapeDtypeStruct((2,), jnp.uint32)
_TEMPERATURE = jax.ShapeDtypeStruct((), jnp.float32)


class _Once:
    """One of an engine's jitted programs, lowered ONCE — at its first call
    of each width, with the operands the engine hands it: the text kept, the
    executable called from then on (the engine's own wrapper would trace it
    a second time to run it, and a trace is what a tiny engine costs)."""

    def __init__(self, jitted, tokens_at: int):
        self.jitted, self.at = jitted, tokens_at
        self.texts, self.compiled = {}, {}

    def __call__(self, *args):
        width = np.shape(args[self.at])[-1]
        if width not in self.compiled:
            lowered = self.jitted.lower(*args)
            self.texts[width] = lowered.as_text()
            self.compiled[width] = lowered.compile()
        return self.compiled[width](*args)


def programs_and_bytes(eng) -> tuple:
    """({program: lowered text}, ``served_bytes``) of ``eng``'s own
    builders: ``decode_step`` and ``prefill_step`` as the scenario
    dispatched them, ``prefill_step`` at any other width of
    ``prefill_widths`` and ``verify_step`` (none for a model whose
    ``verify`` refuses) from abstract operands."""
    S, J, G = eng.max_slots, eng.block_tables.shape[1], eng.dp
    head = (eng._params, *eng._pools())
    eng._decode_fn = decode = _Once(eng._build_decode_step(), len(head))
    eng._prefill_fn = prefill = _Once(eng._build_prefill_step(), len(head))
    served = served_bytes(eng)
    texts = {"decode_step": decode.texts[eng._no_fetch.shape[0]]}
    head = (eng._params, *eng._pools())
    for width in eng.prefill_widths:
        texts[f"prefill_step_{width}"] = prefill.texts.get(width) or \
            prefill.jitted.lower(
                *head, _i32(G, width), _i32(G, J), _i32(G), _i32(G), _i32(G),
                *[_i32(G) for _ in eng._no_freeze()], _i32(), _KEY,
                _TEMPERATURE).as_text()
    try:
        texts["verify_step"] = eng._build_verify_step().lower(
            *head, _i32(S, VERIFY_ROWS), _i32(S), _i32(S, J), _KEY,
            _TEMPERATURE).as_text()
    except NotImplementedError:
        pass
    return texts, served


_VALUE = re.compile(r"%[\w.#:]+")
_SUFFIX = re.compile(r"(@[A-Za-z_][\w.]*?)(_\d+)+\b")


def blanked(text: str) -> list:
    """The text's lines with what tracing ORDER alone decides taken out:
    SSA value names, and the numeric suffixes of functions' names."""
    return [_SUFFIX.sub(r"\1", _VALUE.sub("%", line)).strip()
            for line in text.splitlines()]


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def text_hashes(text: str) -> dict:
    return {"exact": _sha(text),
            "order_free": _sha("\n".join(sorted(blanked(text))))}


# --------------------------------------------------------------------- #
# What the programs compute
# --------------------------------------------------------------------- #
def served_bytes(eng) -> bytes:
    """A prompt of three chunks, then two decode iterations: the first
    token and its logits, each iteration's token and logits."""
    vocab = int(eng.model_cfg.vocab_size)
    prompt = np.random.default_rng(1).integers(
        0, vocab, size=2 * CHUNK + 3, dtype=np.int32)
    slot = eng.select_slot(prompt, 4)
    tok, logits = eng.prefill(prompt, slot, return_logits=True,
                              max_new_tokens=4)
    if eng.block_length:
        # A model of blocks: two chunks as far as the last block boundary,
        # then four passes (a first block of one undecided position: a
        # denoise pass and its commit; two denoise passes of the next):
        # each pass's input block and its logits.
        assert eng.last_admit_info(slot)["chunks"] == 2 and tok is None
        eng.activate_block(slot, prompt)
        parts = []
        for _ in range(4):
            blocks, step_logits = eng.decode_once(0.0, return_logits=True)
            parts += [np.asarray(blocks[slot], np.int32).tobytes(),
                      np.asarray(step_logits)[slot].tobytes()]
        eng.release_slot(slot)
        return b"".join(parts)
    assert eng.last_admit_info(slot)["chunks"] == 3
    eng.activate_slot(slot, len(prompt), tok)
    parts = [np.int32(tok).tobytes(), np.asarray(logits).tobytes()]
    for _ in range(2):
        sampled, step_logits = eng.decode_once(0.0, return_logits=True)
        parts += [np.int32(sampled[slot]).tobytes(),
                  np.asarray(step_logits)[slot].tobytes()]
    eng.release_slot(slot)
    return b"".join(parts)


_READ = {}
DUMP = None     # a directory: every program's blanked text is left there


def golden(family: str, arm: str) -> dict:
    """{"programs": {program: its two hashes}, "outputs": sha256} of ONE
    engine of ``family`` under kernel arm ``arm``, built at the first call
    and its reading kept (the hashes, not the texts: megabytes a program):
    every reader in a process shares it."""
    if (family, arm) not in _READ:
        eng = engine(family, ARMS[arm])
        try:
            texts, served = programs_and_bytes(eng)
        finally:
            eng.close()
        _READ[family, arm] = {
            "programs": {name: text_hashes(text)
                         for name, text in texts.items()},
            "outputs": hashlib.sha256(served).hexdigest()}
        for name, text in texts.items() if DUMP else ():
            os.makedirs(DUMP, exist_ok=True)
            with open(os.path.join(DUMP, f"{family}.{arm}.{name}.txt"),
                      "w") as f:
                f.write("\n".join(blanked(text)) + "\n")
    return _READ[family, arm]


if __name__ == "__main__":
    DUMP = sys.argv[2] if len(sys.argv) > 2 else None
    # (``ADDED`` as a third argument: the families later PRs added alone;
    # ``PR62`` / ``PR64`` / ``PR65``: the fixture that PR added; ``ALL``:
    # every fixture, in one file)
    names = {**FAMILIES, **ADDED, **ADDED_BY_PR62, **ADDED_BY_PR64,
             **ADDED_BY_PR65} if "ALL" in sys.argv[3:] else \
        ADDED if "ADDED" in sys.argv[3:] else \
        ADDED_BY_PR62 if "PR62" in sys.argv[3:] else \
        ADDED_BY_PR64 if "PR64" in sys.argv[3:] else \
        ADDED_BY_PR65 if "PR65" in sys.argv[3:] else FAMILIES
    out = {family: {arm: golden(family, arm) for arm in ARMS}
           for family in sorted(names)}
    with open(sys.argv[1], "w") as f:
        f.write(json.dumps(out, indent=1, sort_keys=True) + "\n")
