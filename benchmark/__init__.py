"""The benchmark of the PQ handshake gateway: one cell, one run, one process
that holds the chip (``benchmark/run.py``), with off-chip client processes.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py``; a configuration's suite family and a traffic's
kind are found by the names those files give them: ``suites/<family>.py``
and ``kinds/<kind>.py``.  ``lib/`` holds the general harness and
``reference/`` the plain reference that decides ``correct``.
"""
