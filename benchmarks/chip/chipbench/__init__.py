"""The chip benchmark's yardstick: cell lookup, the translation of a
configuration into the program's spec, trace reduction, peak table,
operation and byte counts, and the comparison with the plain reference that
decides ``correct``.

Everything here imports the system under test (``repro``) only where it
drives it (``chipbench.program`` and ``drivers/``); the references under
``configs/`` import nothing of it.
"""
