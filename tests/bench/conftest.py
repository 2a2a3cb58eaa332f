"""Every test of this directory gets ``bench_helpers.small_root``
wrapped: it shrinks the md5 mixes and configurations by name, and the
wrap shrinks the copy's sha256d mix and configuration too
(``small_sha256d.py``)."""

from __future__ import annotations

import bench_helpers as bh
import pytest
from small_sha256d import shrink_sha256d


@pytest.fixture(autouse=True)
def _small_sha256d(monkeypatch):
    small_root = bh.small_root

    def shrunk(tmp_path):
        root = small_root(tmp_path)
        shrink_sha256d(root)
        return root

    monkeypatch.setattr(bh, "small_root", shrunk)
