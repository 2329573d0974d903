"""The benchmark's own tests: the repository root on the path so that
``bench`` imports, and a tiny benchmark tree as a fixture."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def tiny(tmp_path):
    from benchtree import tiny_tree
    return tiny_tree(tmp_path)
