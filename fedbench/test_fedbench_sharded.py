"""The agent-sharded training path on four virtual CPU devices: a sound run
is correct, and one whose server mean leaves out the exchange between
chips is not. Runs in a child process, which is given the four devices."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

CHILD = r"""
import json, sys
from unittest import mock
sys.path[:0] = [ROOT, ROOT + "/src"]
from fedbench import faults, harness
from fedbench import test_fedbench_cells as cells
root, base = cells.make_tiny(TMP)
bench = harness.load_json(root + "/BENCHMARK.json")
bench["workloads"].append({"name": "tiny.train4", "config": "fleet32k-vpa",
                           "traffic": "periods", "chips": 4, "why": "test"})
cells._dump(root + "/BENCHMARK.json", bench)
out = {}
for name, fault in [("sound", None), ("no_exchange", faults.no_exchange)]:
    with fault() if fault else mock.MagicMock():
        line = cells.run((root, base), "tiny.train4")
    out[name] = {"correct": line["correct"], "count": line["device"]["count"],
                 "compared": line["compared"]}
print(json.dumps(out))
"""


def test_sharded_run_is_correct_and_no_exchange_is_not(tmp_path):
    root = os.path.dirname(HERE)
    code = CHILD.replace("ROOT", repr(root)).replace("TMP", repr(str(tmp_path)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["count"] == 4
    assert out["sound"]["correct"] is True, out["sound"]["compared"]
    assert out["no_exchange"]["correct"] is False, out["no_exchange"]
