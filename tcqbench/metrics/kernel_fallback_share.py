"""Step layer (core/wave.py, kernels/wave_peel): share of the pools whose
step wanted the fused kernel and ran on the XLA composite, from each
pool's ``fallback`` counter, in %.  Nothing to read where the program
records no ``fallback`` or no pool wanted the kernel."""


def read(run):
    pools = [p for p in run["pools"] if "fallback" in p]
    wanted = [p for p in pools
              if p["backend"] == "pallas" or p["fallback"] is not None]
    if not wanted:
        return None
    return 100.0 * sum(p["fallback"] is not None for p in wanted) \
        / len(wanted)
