"""A tiny training cell in a scratch benchmark root, for tests on the CPU.

The root holds its own ``BENCHMARK.json``, configuration, traffic mix and
limits, and copies of the architecture modules, drivers and metric
readers, so a test can add a file there and see the harness find it
without touching the repository.
"""

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parent.parent
REPO = BENCH.parent
if str(REPO / "src") not in sys.path:     # the program under test
    sys.path.insert(0, str(REPO / "src"))

CELL = "tiny.t32"
# Limits of the tiny cell: the program reads a loss gap near 1e-5 here and
# the fp8 control 3e-4 or more; the norm gaps of a tiny model are noisy.
LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 0.05, "update_norm_gap": 0.08}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    root = pathlib.Path(tmp)
    for sub in ("arch", "drivers", "metrics"):
        shutil.copytree(BENCH / sub, root / "bench" / sub)
    for sub in ("configs", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True)
    cfg = json.loads((BENCH / "configs" / "qwen2.5-3b-l9.json").read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=128, vocab_size=512, num_hidden_layers=2)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    tr = json.loads((BENCH / "traffic" / "s256.json").read_text())
    tr.update(seq=32)
    (root / "bench" / "traffic" / "t32.json").write_text(json.dumps(tr))
    (root / "bench" / "limits" / f"{CELL}.json").write_text(
        json.dumps(LIMITS))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(bench["configs"][0], name="tiny",
                             file="bench/configs/tiny.json")]
    bench["workloads"] = [{"name": CELL, "config": "tiny",
                           "traffic": "t32", "chips": 1, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
