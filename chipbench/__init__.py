"""Chip benchmark of the DSGD-AAU event engine (``DecentralizedTrainer.run``).

Everything that defines the yardstick lives here and is found by name:
``configs/<config>.json``, ``traffic/<mix>.json``, ``models/<model>.py``,
``metrics/<metric>.py``, ``limits/<cell>.json`` and ``peaks.json``.  Run a
cell with ``python -m chipbench.run --workload <cell> --seed <n> --seconds
<s> --trace <0|1>`` from the repository root, on a machine with a TPU.
"""
