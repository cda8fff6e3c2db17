"""The chip benchmark of the weather-stencil system: one command
(``python3 bench/run.py``), cells named in ``BENCHMARK.json``, and the files
each cell is built from (``configs/``, ``traffic/``, ``drivers/``,
``metrics/``)."""
