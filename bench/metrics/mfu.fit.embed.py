"""``mfu.fit`` of the ``embed.fit`` cell, which reports its fit time as
``fit_s.embed``: read as ``bench/metrics/mfu.fit.py`` reads it. Moves
``fit_s.embed``."""
from bench.lib.catalog import reader

read = reader("mfu.fit")
