"""Golden pins: one SHA-256 per library scenario of its per-policy report.

Every scenario's report is a pure function of its spec (virtual clock,
pinned seeds), so each policy's metrics hash to one fixed digest. The pins
in `tests/fixtures/library_pins.json` hold the engine loop, the wave chooser,
the completion drain and the fabric's heap to the timeline they produced
when the pins were written; any change to a scheduling decision, a queue
charge or an event's order moves at least one digest. Only the `policies`
part of the report is hashed: the spec echo carries the engine's option
fields, which are not behaviour.

Each scenario runs at `scenario_sizes.sized`, as the other library-wide
sweeps do.

Regenerate the fixture (only for a change that is meant to alter the
timeline, and say so where the change is recorded) from the repo root:

    PYTHONPATH=src python tests/test_library_pins.py --write
"""
import hashlib
import json
import sys
from pathlib import Path

import pytest

from repro.scenarios import SCENARIOS, ScenarioRunner, get
from scenario_sizes import sized

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "library_pins.json"


def pin(name: str) -> str:
    report = ScenarioRunner(sized(get(name))).run().to_dict()
    blob = json.dumps(report["policies"], sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_report_matches_pin(name):
    assert pin(name) == json.loads(FIXTURE.read_text())[name]


if __name__ == "__main__":
    if sys.argv[1:] != ["--write"]:
        sys.exit("usage: PYTHONPATH=src python tests/test_library_pins.py --write")
    FIXTURE.write_text(json.dumps(
        {n: pin(n) for n in sorted(SCENARIOS)}, indent=1, sort_keys=True) + "\n")
    print(f"wrote {len(SCENARIOS)} pins to {FIXTURE}")
