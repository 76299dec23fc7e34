#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Prints, as its last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
``--trace 1``, ``breakdown``), the compared numbers under ``checked``; the
same comparisons are the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``.
"""
import time

STARTED = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))


def _prepare() -> None:
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _HERE]
    sys.path[:0] = [os.path.join(_ROOT, "src"), os.path.dirname(_HERE)]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT,
                                                           ".jax_cache")


if __name__ == "__main__":
    _prepare()
    from chip.harness import BenchError, main
    import jax
    from repro import compat
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compat.enable_compile_cache()
    try:
        sys.exit(main(started=STARTED))
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        sys.exit(1)
