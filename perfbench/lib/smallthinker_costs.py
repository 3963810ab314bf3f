"""Operations and bytes the ``smallthinker`` configuration's two kernels
need, from shapes and counts alone (``sizes`` is the configuration file's
dict), whatever implements them; and the readings of the traced run the
cell's per-layer metrics share.  Nothing here imports the program.

- The paged attend with grouped heads reads, per key row IN REACH and
  layer, one K and one V row of every K/V head (``num_key_value_heads`` x
  ``head_dim`` values each) whatever the number of query heads (7 a K/V
  head here), and spends per such row and QUERY head a score product and a
  value product of ``head_dim``.  The rows are counted in reach (a window
  layer's stream counts at most ``sliding_window_size`` of them), whatever
  the kernel walks: one that reads past the window reads a LOW share, never
  one over 100%.
- The grouped ReLU-gated product over ALL experts reads the three ``[F,
  H]`` matrices of every expert that got at least one row, reads and writes
  each routed row once, and spends three H x F products a routed (token,
  expert) pair.

A ``prefill`` span carries the expert counters of the chunk program that
ENDED its prompt only (they ride that program's token fetch); the chunk
programs before it are full: ``prefill_chunk`` live rows each, every expert
of every layer hit (512 rows x 6 of 64 experts: an expert without a row is
a 1-in-10^21 event).  ``prefill_expert_load`` puts the two together.
"""
from perfbench.lib import retention_trace

_ITEMSIZE = 2                     # bf16 weights and pools (assumed.dtype)
KEYS = ("hidden_size", "moe_ffn_hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "moe_num_primary_experts",
        "moe_num_active_primary_experts", "num_hidden_layers",
        "sliding_window_size")
# The program's outermost named scopes (inference/smallthinker.py and the
# engine's sampling / block copy): they do not nest in one another.
OUTERMOST = ("embed", "attn", "moe", "lm_head", "sample", "cow_copy")
PROGRAMS = ("decode_step", "prefill_step")


def kv_row_bytes(sizes: dict) -> int:
    """K and V of one token and layer."""
    return 2 * int(sizes["num_key_value_heads"]) * int(sizes["head_dim"]) \
        * _ITEMSIZE


def attend_bytes(sizes: dict, rows_in_reach: float) -> float:
    """Pool bytes for ``rows_in_reach`` key rows (summed over streams AND
    layers, each layer's as far as it reaches: the ``decode`` span's
    ``context_tokens_in_reach``)."""
    return float(rows_in_reach) * kv_row_bytes(sizes)


def attend_flops(sizes: dict, rows_in_reach: float) -> float:
    """Scores and values, 2 FLOPs a multiply-add, for every query head."""
    return float(rows_in_reach) * int(sizes["num_attention_heads"]) \
        * 2 * int(sizes["head_dim"]) * 2


def expert_gemm_bytes(sizes: dict, experts_with_rows: float,
                      pairs: float) -> float:
    H, F = int(sizes["hidden_size"]), int(sizes["moe_ffn_hidden_size"])
    return float(experts_with_rows) * 3 * H * F * _ITEMSIZE \
        + float(pairs) * 2 * H * _ITEMSIZE


def expert_gemm_flops(sizes: dict, pairs: float) -> float:
    H, F = int(sizes["hidden_size"]), int(sizes["moe_ffn_hidden_size"])
    return float(pairs) * 6 * H * F


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: dict) -> float:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) over the kernel's time, in
    percent."""
    floor = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / seconds


def expert_cells(sizes: dict) -> int:
    """(expert, layer) pairs a program walks: every layer routes."""
    return int(sizes["num_hidden_layers"]) \
        * int(sizes["moe_num_primary_experts"])


def prefill_expert_load(sizes: dict, spans, chunk_rows: int):
    """(routed pairs, experts x layers with a row) of a MEAN chunk program,
    from the traced ``prefill`` spans ``[(start, duration, args)]`` (module
    docstring), or None where no span carries the counters."""
    k = int(sizes["moe_num_active_primary_experts"])
    layers, cells = int(sizes["num_hidden_layers"]), expert_cells(sizes)
    programs = pairs = with_rows = 0.0
    for _, _, a in spans:
        if not all(isinstance(a.get(n), (int, float)) for n in (
                "chunks", "slots", "moe_held_pairs", "moe_held_empty")):
            continue
        last, full = float(a["slots"]), float(a["chunks"] - a["slots"])
        programs += a["chunks"]
        pairs += a["moe_held_pairs"] + full * chunk_rows * k * layers
        with_rows += last * cells - a["moe_held_empty"] + full * cells
    if not programs:
        return None
    return pairs / programs, with_rows / programs


def executions(record, program: str) -> int:
    """Executions of ``program`` the traced window holds (one the window's
    edge cut counts whole: a 4 s window holds hundreds)."""
    tr = (record or {}).get("trace") or {}
    return sum(n for name, n in (tr.get("modules") or {}).items()
               if program in name)


def ms_per_execution(record, program: str, scope: str = ""):
    """Device self milliseconds under ``scope`` (all of it for "") inside
    ``program``, per execution; None where the trace holds neither."""
    n = executions(record, program)
    secs = retention_trace.seconds(record, program=program, scope=scope)
    if not n or not secs:
        return None
    return 1e3 * secs / n
