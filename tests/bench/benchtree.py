"""A tiny copy of the benchmark tree, for runs of the harness on the
CPU."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

#: sm-cnn at a size a CPU test run holds; the cells' own files give the rest.
TINY_MODEL = {"vocab_size": 500, "embed_dim": 8, "conv_filters": 12,
              "n_hidden": 28, "max_len": 16}


def tiny_tree(dst: Path) -> Path:
    """A benchmark tree under ``dst``: the repository's BENCHMARK.json and
    bench/ files, every configuration cut to a CPU-sized corpus and model,
    and ``src`` linked in."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "src").symlink_to(ROOT / "src")
    for conf in spec["configs"]:
        path = dst / conf["file"]
        c = json.loads(path.read_text())
        c["model"].update(TINY_MODEL)
        c["corpus"].update(n_docs=600 // c["corpus"]["sents_per_doc"] * 4,
                           vocab_words=3000)
        c["pipeline"]["retrieve_h"] = min(c["pipeline"]["retrieve_h"], 50)
        c["check"]["queries"] = 6
        path.write_text(json.dumps(c))
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst
