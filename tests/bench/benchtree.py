"""A tiny copy of the benchmark tree, for runs of the harness on the
CPU."""
import json
import shutil
from pathlib import Path

from bench import families

ROOT = Path(__file__).resolve().parents[2]


def tiny_tree(dst: Path, src: Path = ROOT) -> Path:
    """A benchmark tree under ``dst``: ``src``'s BENCHMARK.json and bench/
    files, every configuration cut to a CPU-sized corpus and to its model
    family's ``TINY`` size, and the repository's ``src`` linked in."""
    spec = json.loads((src / "BENCHMARK.json").read_text())
    shutil.copytree(src / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "src").symlink_to(ROOT / "src")
    for conf in spec["configs"]:
        path = dst / conf["file"]
        c = json.loads(path.read_text())
        c["model"].update(families.load(dst, c).TINY)
        c["corpus"].update(n_docs=600 // c["corpus"]["sents_per_doc"] * 4,
                           vocab_words=3000)
        c["pipeline"]["retrieve_h"] = min(c["pipeline"]["retrieve_h"], 50)
        c["check"]["queries"] = 6
        path.write_text(json.dumps(c))
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst
