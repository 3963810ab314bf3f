"""One general generator per kind of traffic; a mix is a data file of
its parameters (``perfbench/traffic/<name>.json``).

Everything is drawn from ``seed`` with numpy's ``default_rng``: the same
seed gives the same inputs.  Every seed gets the SAME multiset of
lengths and arrival gaps in another order, so two seeds offer the same
work and differ only in how it interleaves.  The multiset is not sampled:
it is the n stratified quantiles of the stated distribution (log-normal
lengths, the exponential gaps of a Poisson process), so a window holds
the distribution's own mix of short and long without the sampling noise
of n draws.  Lengths, gaps and the shared-prompt flags are dealt so that
every eight consecutive requests hold one of each octile, the same eight
for every seed, in an order the seed shuffles.  That is more regular than
a sampled Poisson stream, on purpose: the benchmark compares two builds of
the program on the same work, and in a saturated continuous batch the
order alone moves the rate by some per cent (PERF.md section 6).

The open-loop arrival idea and the shared-system-prompt shape are copied
from the program's ``inference/scheduler.py`` (``synthetic_requests`` /
``shared_prefix_requests``), whose lengths are uniform and whose output
length is one fixed number.
"""
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


# ------------------------------------------------------------------ #
# kind: train
# ------------------------------------------------------------------ #
def corpus_offsets(n_bytes: int, seed: int, seq_len: int, rows: int,
                   steps: int) -> np.ndarray:
    """Start offsets int64 [steps, rows] of next-token windows of
    ``seq_len + 1`` bytes, uniform over the corpus."""
    hi = n_bytes - (seq_len + 1)
    if hi <= 0:
        raise ValueError(f"corpus of {n_bytes} bytes is shorter than one "
                         f"window of {seq_len + 1}")
    return np.random.default_rng(seed).integers(0, hi, size=(steps, rows))


def corpus_batch(raw: np.ndarray, offsets: np.ndarray,
                 seq_len: int) -> np.ndarray:
    """uint8 corpus + offsets [rows] -> int32 [rows, seq_len + 1]."""
    idx = offsets[:, None] + np.arange(seq_len + 1)[None, :]
    return raw[idx].astype(np.int32)


# ------------------------------------------------------------------ #
# kind: serve
# ------------------------------------------------------------------ #
def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, median: float, sigma: float, lo: int,
                      hi: int) -> np.ndarray:
    """The n stratified quantiles of a log-normal, clipped to [lo, hi]."""
    z = np.array([_NORMAL.inv_cdf(float(u)) for u in _strata(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """The n stratified quantiles of the exponential inter-arrival gap
    of a Poisson process at ``rate`` per second."""
    return -np.log1p(-_strata(n)) / rate


def _zipf_counts(n: int, k: int, exponent: float) -> np.ndarray:
    """n items over k ranks with weight rank**-exponent, by largest
    remainder, so the counts are the same for every seed."""
    w = 1.0 / np.arange(1, k + 1) ** exponent
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts


def _balanced_order(values: np.ndarray, rng, k: int = 8) -> np.ndarray:
    """Deal ``values`` so that every run of k consecutive items holds one
    value from each k-quantile bin, the same k values for every seed;
    the seed only shuffles each run of k.  Round r takes the r-th value
    of the even bins counted from below and of the odd bins from above,
    so the runs' sums stay level along the stream.  Any stretch of the
    stream (its first and last seconds among them) then carries the same
    mix of short and long, whatever the seed."""
    bins = np.array_split(np.sort(values), k)
    out = []
    for r in range(max(len(b) for b in bins)):
        run = [b[r] if j % 2 == 0 else b[len(b) - 1 - r]
               for j, b in enumerate(bins) if r < len(b)]
        out.extend(rng.permutation(run))
    return np.array(out)


def serve_requests(spec: dict, seed: int, seconds: float,
                   vocab_size: int) -> list:
    """Open-loop requests over ``[0, seconds)``: a list of dicts
    ``rid, arrival_s, prompt (int32 array), max_new_tokens, shared``
    (index of the shared system prompt, -1 for none), by arrival."""
    rate = float(spec["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(seed)     # the order, then the tokens
    p, o, sh = spec["prompt_len"], spec["output_len"], spec["shared_prefix"]
    prompt_len = _balanced_order(lognormal_lengths(
        n, p["median"], p["sigma"], p["min"], p["max"]), rng)
    out_len = _balanced_order(lognormal_lengths(
        n, o["median"], o["sigma"], o["min"], o["max"]), rng)
    gaps = _balanced_order(exponential_gaps(n, rate), rng)
    # First arrival at 0; the i-th is due after the first i gaps, so the
    # k-th, 2k-th, ... arrivals are due at the same time for every seed.
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    arrivals *= min(1.0, seconds * (n - 1) / n / gaps.sum())

    n_shared = int(round(sh["share"] * n)) if sh["count"] else 0
    which = np.full(n, -1)
    which[:n_shared] = np.repeat(
        np.arange(sh["count"]),
        _zipf_counts(n_shared, sh["count"], sh["zipf_exponent"])) \
        if n_shared else []
    which = _balanced_order(which, rng)
    system = rng.integers(0, vocab_size, size=(sh["count"], sh["tokens"]),
                          dtype=np.int32)
    out = []
    for i in range(n):
        plen, k = int(prompt_len[i]), int(which[i])
        if k >= 0:
            plen = min(max(plen, sh["tokens"] + p["min"]), p["max"])
        prompt = rng.integers(0, vocab_size, size=plen, dtype=np.int32)
        if k >= 0:
            prompt[:sh["tokens"]] = system[k]
        out.append({"rid": i, "arrival_s": float(arrivals[i]),
                    "prompt": prompt,
                    "max_new_tokens": int(min(out_len[i],
                                              spec["max_total"] - plen)),
                    "shared": k})
    return out


def length_summary(requests: list) -> dict:
    pl = np.array([len(r["prompt"]) for r in requests])
    ol = np.array([r["max_new_tokens"] for r in requests])
    q = (5, 50, 95, 100)
    return {"n": len(requests),
            "prompt_len_p5_p50_p95_max": np.percentile(pl, q).tolist(),
            "output_len_p5_p50_p95_max": np.percentile(ol, q).tolist(),
            "prompt_tokens": int(pl.sum()), "output_tokens": int(ol.sum()),
            "shared": int(sum(r["shared"] >= 0 for r in requests)),
            "last_arrival_s": requests[-1]["arrival_s"]}
