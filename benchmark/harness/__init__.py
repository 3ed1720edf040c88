"""The benchmark's yardstick: everything that decides a number lives here
(traffic generation, client clock, metric arithmetic, trace reduction, peaks,
operation/byte counts). From the program it takes only the system under test
and its spans, counters and kernel names (PERF.md, "what the benchmark
touches")."""
