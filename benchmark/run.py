"""Entry point: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout (see
``harness.py``)."""

import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# JAX's persistent compilation cache, at a fixed path in the checkout:
# set before JAX is imported, so that JAX and the program take it
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    ROOT, ".bench_jax_cache")
sys.path[0] = ROOT

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    try:
        code = harness.run(sys.argv[1:], T_START)
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # every node has been shut down and the result printed; do not wait
    # on interpreter teardown of the runtime's own threads
    os._exit(code)
