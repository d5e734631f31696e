"""``cells_per_request`` in the cells on the XLA composite path, whose few long
requests a window report their latency as a mean."""

from tcqbench.registry import Registry

read = Registry().reader("cells_per_request")
