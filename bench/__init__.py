"""The chip benchmark: one cell of ``BENCHMARK.json`` per run of
``bench/run.py``; see ``bench/spec.py`` for how its pieces are found."""
