"""perfbench/runners/serve.summarize: the cut window's numbers from the
scheduler's Request clocks."""
import numpy as np

from deepspeed_tpu.inference.scheduler import Request
from perfbench.runners import serve


def _req(rid, arrival, out=0, want=10, t_admit=None, t_first=None,
         t_last=None):
    r = Request(rid=rid, prompt=np.zeros(4, np.int32), max_new_tokens=want,
                arrival_s=arrival)
    r.t_arrival = 100.0 + arrival
    r.t_admit, r.t_first, r.t_last = t_admit, t_first, t_last
    r.out_tokens = list(range(out))
    return r


def test_a_request_without_a_slot_counts_with_the_time_it_waited():
    reqs = [_req(0, 0.0, out=10, t_admit=100.5, t_first=101.0, t_last=110.0),
            _req(1, 2.0, out=3, t_admit=104.0, t_first=105.0, t_last=107.0),
            _req(2, 4.0), _req(3, 8.0),
            _req(4, 11.0)]                    # due after the cut: not attempted
    s = serve.summarize(reqs, 10.0)
    assert s["attempted"] == 4 and s["started"] == 2 and s["completed"] == 1
    assert s["failed"] == 0 and s["output_tokens"] == 13
    assert s["tokens_per_s"] == 1.3
    # first-token times 1000, 3000 and the censored waits 6000, 2000 ms
    assert s["ttft_p50_ms"] == np.percentile([1000, 3000, 6000, 2000], 50)
    assert s["ttft_p95_ms"] == np.percentile([1000, 3000, 6000, 2000], 95)
    assert s["queue_wait_p95_ms"] == np.percentile([500, 2000, 6000, 2000], 95)
    # time per output token: admitted requests only
    assert s["tpot_p95_ms"] == np.percentile([1000.0, 1000.0], 95)


def test_admitting_fewer_does_not_flatter_the_tails():
    served = [_req(i, float(i), out=5, t_admit=100.0 + i + 0.1,
                   t_first=100.0 + i + 0.2, t_last=100.0 + i + 1) for i in range(8)]
    starved = served[:2] + [_req(i, float(i)) for i in range(2, 8)]
    assert serve.summarize(starved, 10.0)["ttft_p95_ms"] > \
        serve.summarize(served, 10.0)["ttft_p95_ms"]


def test_too_many_tokens_is_a_failure():
    r = _req(0, 0.0, out=12, want=10, t_admit=100.1, t_first=100.2, t_last=105.0)
    assert serve.summarize([r], 10.0)["failed"] == 1
