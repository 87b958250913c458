"""Chip benchmark of the D-SGD step of ``make_train_setup``.

Everything that belongs to one configuration, traffic mix, metric or cell
is a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``
and ``limits/<cell>.json``. ``run.py`` runs one cell once.
"""
