"""``bytes_synced_per_step`` in the YouTube-growth cell, whose windows run the XLA
composite over the window's own rows and whose few long requests a window
report their latency as a mean."""

from tcqbench.registry import Registry

read = Registry().reader("bytes_synced_per_step")
