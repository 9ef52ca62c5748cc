"""The chip benchmark of raphtory_tpu (see BENCHMARK.json and PERF.md).

Everything the yardstick is made of lives here: data and traffic
generation, the plain numpy reference, the REST client, the reduction
from spans, ledgers and the profiler trace to metrics, and the table of
peaks. From the program it takes only the system under test
(``NodeRuntime`` behind REST) and its spans, counters and kernel names.
"""


class BenchFailure(RuntimeError):
    """The run cannot produce a result: exit non-zero, print none."""
