import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
# keep the CPU tests' compiled programs out of the benchmark's own cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(Path(__file__).parent / ".jax_cache"))


def tiny(cell, sf: float = 0.01):
    """``cell`` at scale factor ``sf``: what a CPU test can hold."""
    cell.config["scale_factor"] = sf
    return cell


def cell_of(config: str, traffic: str, chips: int, sf: float = 0.01):
    """A cell of the benchmark's files at scale factor ``sf``, whether or not
    ``BENCHMARK.json`` lists it."""
    import json

    import cell

    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return tiny(cell.make(f"{config}.{traffic}", config, traffic, chips, bench), sf)
