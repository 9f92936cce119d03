"""Run the benchmark configs (BASELINE's six + framework extras); one JSON line each.

Usage: ``python benchmarks/run_all.py [config_numbers...]``
(no args = all). Runs on whatever backend jax selects; every line says
which (``platform`` / ``device_kind`` / ``device_count``). Every config
runs even after one raised, and the exit code is non-zero if any did.
"""

import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.configs import ALL_CONFIGS


def main() -> int:
    from tensorframes_tpu.utils.profiling import device_stamp

    which = [int(a) for a in sys.argv[1:]] or sorted(ALL_CONFIGS)
    failed = 0
    for i in which:
        try:
            res = ALL_CONFIGS[i]()
        except Exception as e:  # keep going; report and count the failure
            traceback.print_exc()
            failed += 1
            res = {"metric": f"config{i}", "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(device_stamp() | res), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
