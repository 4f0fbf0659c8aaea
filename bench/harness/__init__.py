"""The benchmark's yardstick: everything that turns a run into numbers.

Traffic generation, weight generation, the reference's driver and its
comparison, the table of chip peaks and the reduction of a profiler trace
live here, and each model family's plain reference and operation counts
beside it in ``bench/families``, so that a change to the program under
test cannot move them.
"""
