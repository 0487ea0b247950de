"""The repo's benchmark: one RL cell per process, through the normal path.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a later PR may not move lives here: traffic generation, the
benchmark's own clock and spans, the reduction from the profiler's trace
to numbers, the table of peaks, the FLOP and byte arithmetic, each
architecture's plain reference and the comparison that decides
`correct`.  From the program it takes the system under test
(`build_ppo_math` + `run_experiment_inproc`), its step stats and its
engines' counters.  See PERF.md.
"""
