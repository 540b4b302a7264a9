"""Every configuration in BENCHMARK.json is its own deployment: no two share
a source and a list of reduced keys, nor a world and an agent."""
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    CONFIGS = json.load(f)["configs"]


def _body(c):
    with open(os.path.join(REPO, c["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("key", ["source_and_reduced", "world_and_agent"])
def test_no_two_configurations_alike(key):
    seen = {}
    for c in CONFIGS:
        if key == "source_and_reduced":
            sig = (c["source"], tuple(sorted(c["reduced"])))
        else:
            b = _body(c)
            sig = json.dumps([b["world"], b["agent"]], sort_keys=True)
        assert sig not in seen, f"{c['name']} repeats {seen.get(sig)}"
        seen[sig] = c["name"]
