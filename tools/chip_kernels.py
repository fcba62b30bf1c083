"""Run the kernel differential tests with the COMPILED kernels on the chip.

tests/test_scan_pallas.py and tests/test_compact_pallas.py compare the
Pallas kernels with the jnp kernels (and from-scratch numpy oracles) under
the Pallas interpreter, pinned to the CPU by tests/conftest.py. That proves
the math; it says nothing about what Mosaic compiles. This runs the same
tests on the attached accelerator with every ``pallas_call`` forced out of
interpret mode, so cross-tile carries, TTL chains longer than a tile and the
query-batched grid are checked as the chip executes them.

    python tools/chip_kernels.py [pytest args ...]

Needs the chip to itself (one process per chip); fails without one.
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_TESTS = ["tests/test_scan_pallas.py", "tests/test_compact_pallas.py"]


def main(argv: list[str]) -> int:
    sys.path.insert(0, REPO)
    os.chdir(REPO)
    # no silent CPU fallback: without a chip the first device touch fails
    os.environ.setdefault("JAX_PLATFORMS", "tpu")

    import pytest
    from jax.experimental import pallas as pl

    from kubebrain_tpu.util.jaxcache import use_compile_cache

    use_compile_cache()
    interpreted = pl.pallas_call

    def compiled(*args, **kw):
        kw["interpret"] = False
        return interpreted(*args, **kw)

    pl.pallas_call = compiled
    # --noconftest: tests/conftest.py pins the platform to the CPU
    return pytest.main(["--noconftest", "-p", "no:cacheprovider", "-q",
                        *(argv or DEFAULT_TESTS)])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
