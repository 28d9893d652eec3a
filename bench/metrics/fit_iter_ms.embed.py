"""``fit_iter_ms`` of the ``embed.fit`` cell, which reports its fit time as
``fit_s.embed``: read as ``bench/metrics/fit_iter_ms.py`` reads it. Moves
``fit_s.embed``."""
from bench.lib.catalog import reader

read = reader("fit_iter_ms")
