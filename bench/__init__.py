"""The chip benchmark (see ``bench/run.py`` and ``BENCHMARK.json``)."""
