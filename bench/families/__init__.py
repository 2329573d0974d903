"""Model families: what the harness, the reference and the control need
of a configuration's model, one module a family.

A configuration file names its family under ``"family"``; one that names
none is ``sm-cnn``. The family's module is ``bench/families/<family>.py``
with dashes as underscores, found by path in the tree the cell came from,
as a metric's reader is. It gives, at module level:

  TINY                        the model keys a CPU test run holds
  program_config(config)      the program's config object
  init_params(key, cfg)       the device weights (the caller jits it)
  warm(pool, cfg, buckets)    compile and warm each replica's scorer
  reference_weights(key_bits, model)
                              the reference's own weights, from the seed
  reference_logits(W, corpus, query_words, ids, model)
                              float64 logits, one per candidate sentence
  served_logit(scores)        a served score as a logit
  control_weights(W), control_scores(w, W, corpus, query_words, ids, model)
                              the lower-precision control: its weights,
                              and its served scores per candidate sentence

So a configuration of a new family is a new module and a new entry.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from typing import Dict

DEFAULT = "sm-cnn"


def path(root: Path, config: Dict) -> Path:
    """The module file of ``config``'s family under ``root``."""
    name = config.get("family", DEFAULT).replace("-", "_")
    return Path(root) / "bench" / "families" / f"{name}.py"


def load(root: Path, config: Dict):
    """The family module of ``config``, from ``root``'s tree."""
    file = path(root, config)
    if not file.is_file():
        raise FileNotFoundError(
            f"no module for model family "
            f"{config.get('family', DEFAULT)!r}: looked for {file}")
    name = f"bench_family_{file.stem}"
    spec = importlib.util.spec_from_file_location(name, file)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module
