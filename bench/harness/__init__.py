"""The benchmark's yardstick: everything that turns a run into numbers.

Traffic generation, weight generation, the plain reference and its
comparison, model and kernel operation counts, the table of chip peaks and
the reduction of a profiler trace all live here, beside the benchmark, so
that a change to the program under test cannot move them.
"""
