"""Fixtures of the harness rehearsals (helpers in ``tiny_cells.py``)."""

import os

import pytest

from tiny_cells import write_tree


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """chipbench.run pointed at a tree of tiny cells."""
    from chipbench import run

    root = write_tree(str(tmp_path))
    monkeypatch.setattr(run, "ROOT", root)
    monkeypatch.setattr(run, "BENCH", os.path.join(root, "chipbench"))
    monkeypatch.setattr(run, "CACHE", os.path.join(root, ".cache"))
    return run
