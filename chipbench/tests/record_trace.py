"""Records, on the chip, the small trace that ``test_tracereduce`` reads:

    python3 chipbench/tests/record_trace.py

One traced ``detnet.train`` run with a window of 2 s and a traced stretch
of 0.3 s. The trace goes to ``data/detnet.train.xplane.pb.gz``, the host
spans to ``data/detnet.train.spans.json``, and the run's result line, whose
``device`` and ``breakdown`` the test compares with its own reduction, to
``data/detnet.train.result.json``.
"""
import contextlib
import io
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402


def main() -> int:
    os.makedirs(DATA, exist_ok=True)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", "detnet.train", "--seed", "2718281828",
                       "--seconds", "2", "--trace", "1"],
                      overrides=lambda cfg, tr: (cfg, dict(tr, trace_seconds=0.3)),
                      keep_trace=os.path.join(DATA, "detnet.train"))
    if rc == 0:
        with open(os.path.join(DATA, "detnet.train.result.json"), "w") as fh:
            fh.write(out.getvalue().strip().splitlines()[-1] + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
