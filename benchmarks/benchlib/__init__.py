"""The benchmark's yardstick: traffic generation, statistics, the table
of peaks, operation and byte counts, the reduction from a profiler trace
to metrics, the plain float32 references and the comparisons that decide
`correct`. Nothing here imports the program (`paddle_tpu`) except the two
drivers (`serve.py`, `train.py`), which call its public entry points."""
