"""What both runners need: the model configuration from a configuration
file's published keys, the seeded weights, memory and trace helpers."""
import os
import shutil

import jax


def model_config(sizes: dict, **options):
    """The program's GPT2Config from the published config.json keys."""
    from deepspeed_tpu.models import GPT2Config
    return GPT2Config(
        hidden_size=sizes["n_embd"], num_heads=sizes["n_head"],
        num_layers=sizes["n_layer"], max_seq_length=sizes["n_positions"],
        vocab_size=sizes["assumed"]["vocab_rows_held"],
        layer_norm_eps=sizes["layer_norm_epsilon"],
        initializer_range=sizes["initializer_range"],
        hidden_dropout=sizes["resid_pdrop"],
        attn_dropout=sizes["attn_pdrop"], **options)


def seeded_params(cfg, seed: int, dtype=None):
    """The weights, made on the device in ONE jitted call from the seed
    (``dtype`` casts the matrices' tree inside the same call)."""
    from deepspeed_tpu.models import gpt2_init

    def init(key):
        params = gpt2_init(key, cfg)
        if dtype is not None:
            params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        return params
    return jax.jit(init)(jax.random.PRNGKey(seed))


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no statistics, i.e. the CPU rehearsal)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def start_trace(trace_dir: str):
    """A fresh profiler session into ``trace_dir``; the Python tracer is
    off (it slows the host loop this trace is there to observe), so the
    host plane holds the runner's TraceAnnotations only."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
