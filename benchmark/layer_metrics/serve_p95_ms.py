"""Serving: 95th percentile of answer time - submit time over the requests
answered in the window. In the closed loop the latencies are multiples of
the pack time, so this reads one of two neighbouring multiples depending on
the seed (4.0 or 4.64 s at 0.66 s a pack, my chip runs, PR 24): recorded,
not bounded. It is there to show a throughput gain bought by holding
requests back. Moves serve_img_per_s."""


def read(run):
    return run.counters.get("serve_latency_p95_ms")
