"""Records, on the chip, the small trace that ``test_programspans`` reads:

    python3 chipbench/tests/record_program_trace.py

One traced ``detnet.train`` run with a window of 2 s and a traced stretch
of 0.3 s, as ``record_trace.py`` makes, with the program's own spans
besides. The trace goes to ``data/detnet.train.program.xplane.pb.gz``, the
benchmark's spans to ``data/detnet.train.program.spans.json``, the
program's spans that overlap the window (``repro.spans.RECORDER``, with
their step numbers) to ``data/detnet.train.program.program_spans.json``,
and the run's result line, whose per-layer metrics the test compares with
its own split, to ``data/detnet.train.program.result.json``.
"""
import contextlib
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
PREFIX = os.path.join(DATA, "detnet.train.program")
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402
import run  # noqa: E402


def main() -> int:
    os.makedirs(DATA, exist_ok=True)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", "detnet.train", "--seed", "3141592653",
                       "--seconds", "2", "--trace", "1"],
                      overrides=lambda cfg, tr: (cfg, dict(tr, trace_seconds=0.3)),
                      keep_trace=PREFIX)
    if rc != 0:
        return rc
    from repro.spans import RECORDER
    (lo, hi, _), = [s for s in harness.span.events if s[2] == "window"]
    with open(f"{PREFIX}.program_spans.json", "w") as fh:
        json.dump(RECORDER.events(lo, hi), fh)
    with open(f"{PREFIX}.result.json", "w") as fh:
        fh.write(out.getvalue().strip().splitlines()[-1] + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
