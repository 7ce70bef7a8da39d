"""The benchmark of `advspec serve`: see PERF.md at the root of the repository.

Everything that decides a number lives here: traffic generation, the
reduction from traces and counters to metrics, the table of peaks, the
shape functions, the plain reference and the comparison behind `correct`.
From the program it takes the system under test, its counters and the
names of its compiled programs and kernels.
"""
