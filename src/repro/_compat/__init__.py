"""Compatibility shim for an optional test dependency.

``hypothesis_stub`` is a minimal property-testing stand-in installed by
``tests/conftest.py`` only when the real ``hypothesis`` package is absent
(offline CI containers).
"""
