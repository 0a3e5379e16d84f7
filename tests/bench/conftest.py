"""A benchmark tree at test size: the real ``bench/`` files, with the
configurations shrunk so that a whole run takes a second on the CPU."""
import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the shrunk sizes: a scale-9 Kronecker graph
TINY = {
    "graph500-19": {"scale": 9},
}


def make_tiny_root(dest: pathlib.Path) -> pathlib.Path:
    """Copy the benchmark to ``dest`` with every configuration shrunk."""
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        path = dest / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(TINY[c["name"]])
        path.write_text(json.dumps(cfg))
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
