"""``queue_wait_p95_ms`` in the YouTube-growth cell, whose windows run the XLA
composite over the window's own rows and whose few long requests a window
report their latency as a mean."""

from tcqbench.registry import Registry

read = Registry().reader("queue_wait_p95_ms")
