"""``decode_step``'s lowered text, hashed, for a tiny engine of every
family that was served before PR 54 (not a test file: the golden hashes in
``tests/data/decode_step_hlo_pr55.json`` were written by running this file
on the tree at PR 55, ``python tests/decode_step_hlo.py``, its last lines; the test
that compares is ``test_smallthinker_serving.py``).  PR 55 changed the text
on purpose and in one place: ``sample``'s select between an argmax and a
draw, both evaluated, became a ``case`` with one of them in each branch —
the only lines that differ from PR 53's text once value numbers are
blanked — so the hashes were written again.

The text is ``jax.jit(...).lower(...).as_text()`` of the ENGINE's own
``_build_decode_step`` — StableHLO without locations, so a named scope or a
moved line of Python changes nothing in it, and an operation added, dropped
or reordered does — once with the Pallas kernels (interpret mode) and once
without, concatenated.
"""
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


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
    return cfg, deepseek_v3_init, {"block_size": 16, "prefill_chunk": 32}


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
    return cfg, afmoe_init, {"num_blocks": {"full": 64, "window": 40}}


def _lfm2():
    from deepspeed_tpu.models.lfm2 import CONV, FULL, Lfm2Config, lfm2_init
    cfg = Lfm2Config(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        num_experts_per_tok=2, layer_types=(CONV, FULL, CONV, CONV, CONV),
        max_position_embeddings=256, dtype=jnp.float32)
    return cfg, lfm2_init, {"num_blocks": {"full": 96, "conv": 16}}


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
    return cfg, falcon_h1_init, {"num_blocks": {"full": 96, "state": 16}}


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
    return cfg, kimi_linear_init, {"num_blocks": {"latent": 96, "state": 16}}


# cell 4 (a held share, group-limited), 7 (several residual streams), 6, 8,
# 9 (no experts: the attention branch only), 10
FAMILIES = {"latent_share": _latent, "latent_hyper": _hyper,
            "afmoe": _afmoe, "lfm2": _lfm2, "falcon_h1": _falcon_h1,
            "kimi_linear": _kimi_linear}


def decode_step_text(family: str, kernel: bool) -> str:
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.parallel.topology import build_mesh
    cfg, init, inference = FAMILIES[family]()
    conf = dict(max_slots=4, max_seq_len=128, block_size=4, prefill_chunk=8,
                paged_kernel=kernel)
    conf.update(inference)
    eng = InferenceEngine(cfg, init(jax.random.PRNGKey(0), cfg),
                          config={"inference": conf},
                          mesh=build_mesh(devices=jax.devices()[:1]))
    try:
        S, J = eng.max_slots, eng.block_tables.shape[1]
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa
        return eng._build_decode_step().lower(
            eng._params, *eng._pools(),
            i32(S + len(eng.served.counter_names)), i32(S),
            jax.ShapeDtypeStruct((S,), jnp.bool_), i32(S), i32(S, J),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((), jnp.float32)).as_text()
    finally:
        eng.close()


def decode_step_sha(family: str) -> str:
    return hashlib.sha256("\n".join(
        decode_step_text(family, kernel)
        for kernel in (False, True)).encode()).hexdigest()


if __name__ == "__main__":
    print(json.dumps({f: decode_step_sha(f) for f in sorted(FAMILIES)},
                     indent=1))
