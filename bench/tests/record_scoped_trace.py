"""Records the trace that ``test_scopes.py`` reads, on a TPU: the tiny
bf16 model of ``tiny.py`` serving one wave of its mix, profiled by
``profile_layers.profile`` with the program's layer scopes and host
spans, and the compiled texts of its programs.

    python3 bench/tests/record_scoped_trace.py bench/tests/data_scoped

Writes ``tiny_scoped.xplane.pb`` and ``hlo.json.gz`` into the directory
and prints the profile's result line.
"""

import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE)]

import profile_layers  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402


def main(out: str) -> int:
    dest = Path(out)
    dest.mkdir(parents=True, exist_ok=True)
    device = run.accelerator(1)[0]
    with tempfile.TemporaryDirectory() as keep:
        # a window of 0 s serves exactly one wave
        result = profile_layers.profile(
            tiny.cell(tiny.DENSE), 5, 0.0, device,
            run.peaks_of(device.device_kind), keep=keep)
        trace, = Path(keep).rglob("*.xplane.pb")
        shutil.copy(trace, dest / "tiny_scoped.xplane.pb")
        shutil.copy(Path(keep) / profile_layers.HLO_FILE, dest)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
