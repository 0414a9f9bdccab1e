"""dssbench — the chip benchmark of TPU-native DSS (BENCHMARK.json).

The yardstick lives here: data generation, traffic, the plain
reference, the comparison that decides `correct`, the reduction from
scrapes and traces to metrics, the table of peaks.  From the program
it takes only the server under test (started as a subprocess), its
/metrics gauges, its boot log and one profiler capture.
"""
