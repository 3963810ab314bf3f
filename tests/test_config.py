"""Config system tests — parity with reference tests/unit/test_config.py and
test_ds_config.py (batch triple inference, duplicate keys, zero config)."""
import json

import pytest

from deepspeed_tpu.runtime.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.runtime.config_utils import loads_config_json


def make_cfg(d, world_size=1):
    return DeepSpeedConfig(d, world_size=world_size)


class TestBatchConfig:
    def test_all_three_given(self):
        cfg = make_cfg({"train_batch_size": 32, "train_micro_batch_size_per_gpu": 4,
                        "gradient_accumulation_steps": 2}, world_size=4)
        assert cfg.train_batch_size == 32
        assert cfg.train_micro_batch_size_per_gpu == 4
        assert cfg.gradient_accumulation_steps == 2

    def test_infer_grad_acc(self):
        cfg = make_cfg({"train_batch_size": 32, "train_micro_batch_size_per_gpu": 4},
                       world_size=4)
        assert cfg.gradient_accumulation_steps == 2

    def test_infer_micro_batch(self):
        cfg = make_cfg({"train_batch_size": 32, "gradient_accumulation_steps": 2},
                       world_size=4)
        assert cfg.train_micro_batch_size_per_gpu == 4

    def test_infer_train_batch(self):
        cfg = make_cfg({"train_micro_batch_size_per_gpu": 4,
                        "gradient_accumulation_steps": 2}, world_size=4)
        assert cfg.train_batch_size == 32

    def test_only_train_batch(self):
        cfg = make_cfg({"train_batch_size": 32}, world_size=4)
        assert cfg.train_micro_batch_size_per_gpu == 8
        assert cfg.gradient_accumulation_steps == 1

    def test_only_micro_batch(self):
        cfg = make_cfg({"train_micro_batch_size_per_gpu": 4}, world_size=4)
        assert cfg.train_batch_size == 16
        assert cfg.gradient_accumulation_steps == 1

    def test_inconsistent_triple_raises(self):
        with pytest.raises(AssertionError):
            make_cfg({"train_batch_size": 33, "train_micro_batch_size_per_gpu": 4,
                      "gradient_accumulation_steps": 2}, world_size=4)

    def test_no_batch_info_raises(self):
        with pytest.raises(DeepSpeedConfigError):
            make_cfg({}, world_size=1)


class TestJsonHandling:
    def test_duplicate_keys_rejected(self):
        with pytest.raises(ValueError):
            loads_config_json('{"train_batch_size": 1, "train_batch_size": 2}')

    def test_file_loading(self, tmp_ds_config):
        path = tmp_ds_config({"train_batch_size": 8})
        cfg = DeepSpeedConfig(path, world_size=1)
        assert cfg.train_batch_size == 8


class TestPrecision:
    def test_fp16(self):
        cfg = make_cfg({"train_batch_size": 8, "fp16": {"enabled": True}})
        assert cfg.fp16_enabled and not cfg.bf16_enabled
        assert cfg.precision_dtype == "float16"

    def test_bf16(self):
        cfg = make_cfg({"train_batch_size": 8, "bf16": {"enabled": True}})
        assert cfg.precision_dtype == "bfloat16"

    def test_both_raises(self):
        with pytest.raises(DeepSpeedConfigError):
            make_cfg({"train_batch_size": 8, "fp16": {"enabled": True},
                      "bf16": {"enabled": True}})

    def test_fp16_defaults(self):
        cfg = make_cfg({"train_batch_size": 8, "fp16": {"enabled": True}})
        assert cfg.fp16_initial_scale_power == 32
        assert cfg.fp16_loss_scale_window == 1000
        assert cfg.fp16_hysteresis == 2
        assert cfg.fp16_min_loss_scale == 1

    def test_amp_maps_to_bf16(self):
        """amp must act, never silently no-op (reference engine.py:630-668
        wraps apex; the TPU equivalent of amp O1 is the bf16 path)."""
        cfg = make_cfg({"train_batch_size": 8, "amp": {"enabled": True}})
        assert cfg.amp_enabled and cfg.bf16_enabled
        assert cfg.precision_dtype == "bfloat16"

    def test_amp_with_bf16_is_idempotent(self):
        cfg = make_cfg({"train_batch_size": 8, "amp": {"enabled": True},
                        "bf16": {"enabled": True}})
        assert cfg.precision_dtype == "bfloat16"

    def test_amp_with_fp16_raises(self):
        with pytest.raises(DeepSpeedConfigError, match="bf16|fp16"):
            make_cfg({"train_batch_size": 8, "amp": {"enabled": True},
                      "fp16": {"enabled": True}})

    def test_amp_disabled_is_inert(self):
        cfg = make_cfg({"train_batch_size": 8, "amp": {"enabled": False}})
        assert not cfg.amp_enabled and not cfg.bf16_enabled


class TestFusedOptimizer:
    def test_fused_default_on(self):
        cfg = make_cfg({"train_batch_size": 8,
                        "optimizer": {"type": "AdamW",
                                      "params": {"lr": 1e-3}}})
        assert cfg.optimizer_fused

    def test_fused_off(self):
        cfg = make_cfg({"train_batch_size": 8,
                        "optimizer": {"type": "AdamW",
                                      "params": {"lr": 1e-3,
                                                 "fused": False}}})
        assert not cfg.optimizer_fused

    def test_build_optimizer_honors_knob(self):
        from deepspeed_tpu.ops.optimizers import build_optimizer
        fused = build_optimizer("adamw", {"lr": 1e-3})
        assert getattr(fused, "fused_apply", None) is not None
        plain = build_optimizer("adamw", {"lr": 1e-3, "fused": False})
        assert getattr(plain, "fused_apply", None) is None
        # fused never hijacks non-Adam or onebit paths
        lamb = build_optimizer("lamb", {"lr": 1e-3})
        assert getattr(lamb, "fused_apply", None) is None
        onebit = build_optimizer("onebitadam", {"lr": 1e-3})
        assert getattr(onebit, "fused_apply", None) is None


class TestZeroConfig:
    def test_defaults(self):
        cfg = make_cfg({"train_batch_size": 8})
        assert cfg.zero_optimization_stage == 0
        assert not cfg.zero_enabled

    def test_stage2_with_offload(self):
        cfg = make_cfg({"train_batch_size": 8,
                        "zero_optimization": {"stage": 2, "cpu_offload": True,
                                              "reduce_bucket_size": 1000}})
        assert cfg.zero_optimization_stage == 2
        assert cfg.zero_config.cpu_offload
        assert cfg.zero_config.reduce_bucket_size == 1000

    def test_legacy_bool(self):
        cfg = make_cfg({"train_batch_size": 8, "zero_optimization": True})
        assert cfg.zero_optimization_stage == 1

    def test_offload_overlap_knobs(self):
        from deepspeed_tpu import constants as C
        cfg = make_cfg({"train_batch_size": 8,
                        "zero_optimization": {
                            "stage": 2, "cpu_offload": True,
                            "overlap_comm": True,
                            "offload_bucket_size": 1 << 20,
                            "offload_host_threads": 3}})
        assert cfg.zero_config.overlap_comm
        assert cfg.zero_config.offload_bucket_size == 1 << 20
        assert cfg.zero_config.offload_host_threads == 3
        # defaults: serial off, ~64 MB buckets, auto threads
        dflt = make_cfg({"train_batch_size": 8,
                         "zero_optimization": {"stage": 2,
                                               "cpu_offload": True}})
        assert not dflt.zero_config.overlap_comm
        assert dflt.zero_config.offload_bucket_size == \
            C.ZERO_OFFLOAD_BUCKET_SIZE_DEFAULT
        assert dflt.zero_config.offload_host_threads == 0
        for bad in [{"offload_bucket_size": 0},
                    {"offload_bucket_size": -4},
                    {"offload_host_threads": -1}]:
            with pytest.raises(ValueError):
                make_cfg({"train_batch_size": 8,
                          "zero_optimization": {"stage": 2, **bad}})

    def test_invalid_stage(self):
        with pytest.raises(ValueError):
            make_cfg({"train_batch_size": 8, "zero_optimization": {"stage": 9}})

    def test_grad_sync_knob(self):
        from deepspeed_tpu import constants as C
        dflt = make_cfg({"train_batch_size": 8,
                         "zero_optimization": {"stage": 2}})
        assert dflt.zero_config.grad_sync == C.ZERO_GRAD_SYNC_DEFAULT == "auto"
        assert dflt.zero_config.reduce_scatter   # default on
        for mode in C.ZERO_GRAD_SYNC_MODES:
            cfg = make_cfg({"train_batch_size": 8,
                            "zero_optimization": {"stage": 2,
                                                  "grad_sync": mode}})
            assert cfg.zero_config.grad_sync == mode

    def test_grad_sync_invalid_value_raises(self):
        with pytest.raises(ValueError):
            make_cfg({"train_batch_size": 8,
                      "zero_optimization": {"stage": 2,
                                            "grad_sync": "hopeful"}})

    def test_reduce_scatter_false_conflicts_with_explicit(self):
        """reduce_scatter: false selects the dense all-reduce path — an
        explicit psum_scatter request alongside it is a contradiction,
        rejected at config parse."""
        with pytest.raises(ValueError):
            make_cfg({"train_batch_size": 8,
                      "zero_optimization": {"stage": 2,
                                            "reduce_scatter": False,
                                            "grad_sync": "explicit"}})
        # but the dense path itself parses fine
        cfg = make_cfg({"train_batch_size": 8,
                        "zero_optimization": {"stage": 2,
                                              "reduce_scatter": False}})
        assert not cfg.zero_config.reduce_scatter


class TestInferenceConfig:
    """The serving tier's `inference` block: every knob is static
    compiled-program shape, so bad values must die at config parse, not
    as a shape error three compiles deep."""

    def test_defaults(self):
        from deepspeed_tpu import constants as C
        cfg = make_cfg({"train_batch_size": 8})
        inf = cfg.inference_config
        assert inf.max_slots == C.INFERENCE_MAX_SLOTS_DEFAULT == 8
        assert inf.max_seq_len == 0          # 0 = model max
        assert inf.quantize == "none"
        assert inf.prefill_chunk == C.INFERENCE_PREFILL_CHUNK_DEFAULT

    def test_explicit_values(self):
        cfg = make_cfg({"train_batch_size": 8,
                        "inference": {"max_slots": 16, "max_seq_len": 256,
                                      "quantize": "int8",
                                      "prefill_chunk": 64}})
        inf = cfg.inference_config
        assert inf.max_slots == 16
        assert inf.max_seq_len == 256
        assert inf.quantize == "int8"
        assert inf.prefill_chunk == 64

    def test_standalone_parse(self):
        """InferenceEngine parses the block from a raw dict without the
        training batch keys — the serving config needs no batch triple."""
        from deepspeed_tpu.runtime.config import InferenceConfig
        inf = InferenceConfig({"inference": {"max_slots": 4,
                                             "quantize": "bf16"}})
        assert inf.max_slots == 4 and inf.quantize == "bf16"
        assert InferenceConfig(None).max_slots == 8
        assert InferenceConfig({}).prefill_chunk == 32

    @pytest.mark.parametrize("bad", [
        {"max_slots": 0}, {"max_slots": -2}, {"max_slots": 2.5},
        {"max_seq_len": -1},
        {"quantize": "fp4"}, {"quantize": True},
        {"prefill_chunk": -8}, {"prefill_chunk": "auto"},
        {"prefill_chunk": 0},
    ])
    def test_invalid_values_raise(self, bad):
        with pytest.raises(DeepSpeedConfigError):
            make_cfg({"train_batch_size": 8, "inference": bad})


class TestOptimizerScheduler:
    def test_optimizer_params(self):
        cfg = make_cfg({"train_batch_size": 8,
                        "optimizer": {"type": "Adam", "params": {"lr": 0.001}}})
        assert cfg.optimizer_name == "adam"
        assert cfg.optimizer_params["lr"] == 0.001

    def test_scheduler_params(self):
        cfg = make_cfg({"train_batch_size": 8,
                        "scheduler": {"type": "WarmupLR",
                                      "params": {"warmup_num_steps": 10}}})
        assert cfg.scheduler_name == "WarmupLR"
        assert cfg.scheduler_params["warmup_num_steps"] == 10


class TestMisc:
    def test_gradient_clipping(self):
        cfg = make_cfg({"train_batch_size": 8, "gradient_clipping": 1.0})
        assert cfg.gradient_clipping == 1.0

    def test_wall_clock_breakdown(self):
        cfg = make_cfg({"train_batch_size": 8, "wall_clock_breakdown": True})
        assert cfg.wall_clock_breakdown

    def test_pld(self):
        cfg = make_cfg({"train_batch_size": 8,
                        "progressive_layer_drop": {"enabled": True, "gamma": 0.01}})
        assert cfg.pld_config.enabled
        assert cfg.pld_config.gamma == 0.01


class TestExampleConfigs:
    def test_all_example_configs_parse(self):
        """examples/ ship runnable ds_configs; keep them valid against the
        config system (batch triple, known keys)."""
        import glob
        import json
        import os
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = glob.glob(os.path.join(here, "examples", "**", "*.json"),
                          recursive=True)
        assert paths, "no example configs found"
        for p in paths:
            with open(p) as f:
                d = json.load(f)
            world = 1
            if "mesh" in d:
                world = (d["mesh"].get("pipe_parallel_size", 1) or 1) * 4
            micro = d.get("train_micro_batch_size_per_gpu")
            if micro:
                world = max(1, d["train_batch_size"] //
                            (micro * d.get("gradient_accumulation_steps", 1)))
            # configs without an explicit micro batch are world-size
            # agnostic: the batch-triple solver derives it (the examples
            # run on 1 real chip or the 8-device CPU mesh unchanged)
            cfg = DeepSpeedConfig(d, world_size=world)
            assert cfg.train_batch_size == d["train_batch_size"], p
