"""The benchmark of the served proof-of-work path (BENCHMARK.json).

Later PRs add a configuration, a traffic mix or a per-layer metric as a
file under ``configs/``, ``traffic/`` or ``layers/`` and an entry in
``BENCHMARK.json``; no file here needs an edit for it.
"""
