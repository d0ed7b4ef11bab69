"""A run with the timed path broken underneath, for the tests: the harness's
look for a chip is skipped (``--rehearsal`` is forced) and the rest of a run
is driven as ever; ``correct`` has to come out false.

    python benchmark/tests/broken_run.py answer_altered --workload ... 

Faults a served cell can have:
  answer_altered   every third answer is changed where it is produced: the
                   engine's decode returns scores and confidences a quarter
                   too large
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def break_answers() -> None:
    from vilbert_multitask_tpu.engine.runtime import InferenceEngine

    sound = InferenceEngine.decode
    calls = {"n": 0}

    def decode(self, req, bundle, row=0):
        result = sound(self, req, bundle, row=row)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            for entry in (result.answers or result.boxes or result.ranking):
                for key in ("score", "confidence"):
                    if key in entry:
                        entry[key] *= 1.25
        return result

    InferenceEngine.decode = decode


FAULTS = {"answer_altered": break_answers}


def main(argv) -> int:
    from benchmark import run

    FAULTS[argv[1]]()
    return run.main([*argv[2:], "--rehearsal"])


if __name__ == "__main__":
    sys.exit(main(sys.argv))
