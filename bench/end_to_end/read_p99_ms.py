"""99th percentile of read latency, submit to answer, over every read
acknowledged in the window."""
from bench.stats import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run.ops_of(("read",))), 99)
