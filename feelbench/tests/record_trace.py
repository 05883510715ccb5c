"""Record the small chip trace ``test_bench_trace.py`` reads.

    python3 feelbench/tests/record_trace.py OUT.xplane.pb.gz

Runs one traced chunk of the ``cnn-das-s8`` path cut to the tests'
tiny size (``tiny.py``) on the chip it finds, and writes the profiler's
XSpace gzipped to OUT.
"""

import gzip
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(out: str) -> int:
    from feelbench import check, run, trace
    from feelbench.tests import tiny
    cell = tiny.cell()
    device = run.check_device(1)
    with tempfile.TemporaryDirectory() as tmp:
        result = run.run(cell, 3, 0.0, True, tmp, device)
        with open(trace.find_xplane(tmp), "rb") as f:
            data = f.read()
    with gzip.open(out, "wb") as f:
        f.write(data)
    check.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
