"""A checkout of the benchmark at tiny sizes, for rehearsals on the CPU.

``make_root(path)`` writes a ``BENCHMARK.json`` with five cells (the
iterate and ViT configurations cut to tiny sizes, one and four devices;
iterate over records stored at the loader's size, and over shards) and a
``bench/`` whose consumers, feeds and metrics are the real ones, linked
file by file, so that a test can add files of its own beside them.
"""

from __future__ import annotations

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"


def make_root(root: pathlib.Path) -> pathlib.Path:
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    for sub in ("consumers", "feeds", "metrics"):
        (root / "bench" / sub).mkdir()
        for f in (BENCH / sub).glob("*.py"):
            (root / "bench" / sub / f.name).symlink_to(f)
    shutil.copy(BENCH / "configs" / "vit_b16-imagenet.reference.py", root / "bench" / "configs")

    it = json.loads((BENCH / "configs" / "iterate-imagenet.json").read_text())
    it.update(name="tiny-iterate", num_records=64, batch_per_chip=8)
    it["loader"].update(hw=[32, 32], out_hw=[24, 24])
    it["check"]["window_batches"] = 3
    vit = json.loads((BENCH / "configs" / "vit_b16-imagenet.json").read_text())
    vit.update(name="tiny-vit", num_records=64, batch_per_chip=4)
    vit["loader"].update(hw=[32, 32], out_hw=[24, 24])
    vit["model"].update(image_size=[24, 24], patch_size=8, hidden_size=32, num_hidden_layers=2,
                        num_attention_heads=2, intermediate_size=64, num_classes=10)
    traffic = json.loads((BENCH / "traffic" / "mixed_sizes.json").read_text())
    traffic["record_sizes"]["rows"] = [[30, 40, 0.5], [40, 30, 0.3], [64, 64, 0.2]]
    traffic["content"]["cell_px"] = 8
    native = json.loads((BENCH / "traffic" / "native256.json").read_text())
    native["record_sizes"]["rows"] = [[32, 32, 1.0]]  # the tiny loader's hw
    native["content"]["cell_px"] = 8
    shards = json.loads((BENCH / "configs" / "iterate-imagenet-shards.json").read_text())
    shards.update(name="tiny-iterate-shards", num_records=64, batch_per_chip=8,
                  loader=it["loader"], check=it["check"])
    for name, obj in (("configs/tiny-iterate", it), ("configs/tiny-vit", vit),
                      ("configs/tiny-iterate-shards", shards), ("traffic/tiny", traffic),
                      ("traffic/tiny_native", native)):
        (root / "bench" / f"{name}.json").write_text(json.dumps(obj, indent=1))

    spec["configs"] = [
        {"name": "tiny-iterate", "source": "test", "file": "bench/configs/tiny-iterate.json",
         "reduced": [], "why": "test"},
        {"name": "tiny-vit", "source": "test", "file": "bench/configs/tiny-vit.json",
         "reduced": [], "why": "test"},
        {"name": "tiny-iterate-shards", "source": "test",
         "file": "bench/configs/tiny-iterate-shards.json", "reduced": [], "why": "test"},
    ]
    spec["workloads"] = [
        {"name": "it.1", "config": "tiny-iterate", "traffic": "tiny", "chips": 1, "why": "test"},
        {"name": "vit.1", "config": "tiny-vit", "traffic": "tiny", "chips": 1, "why": "test"},
        {"name": "vit.4", "config": "tiny-vit", "traffic": "tiny", "chips": 4, "why": "test"},
        {"name": "native.1", "config": "tiny-iterate", "traffic": "tiny_native", "chips": 1,
         "why": "test"},
        {"name": "shards.1", "config": "tiny-iterate-shards", "traffic": "tiny", "chips": 1,
         "why": "test"},
    ]
    cells = [w["name"] for w in spec["workloads"]]
    for m in spec["per_layer"]:
        m["workloads"] = cells
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root
