"""``python3 -m chipbench.selftest``: the benchmark checks itself, on the
CPU, at tiny sizes, on four virtual CPU devices. It prints pass/fail per
test and NO number under the name of a device metric. Exit 0 when all
pass; names given as arguments select tests by substring."""
import os
import sys
import traceback

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

from chipbench.selftest import tests  # noqa: E402


def main() -> int:
    names = [n for n in dir(tests) if n.startswith("test_")]
    want = sys.argv[1:]
    bad = 0
    for n in names:
        if want and not any(w in n for w in want):
            continue
        try:
            getattr(tests, n)()
            print(f"PASS {n}", flush=True)
        except Exception:  # noqa: BLE001 — report and go on
            bad += 1
            traceback.print_exc()
            print(f"FAIL {n}", flush=True)
    print(f"{'FAILED' if bad else 'ok'}: {bad} failed")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
