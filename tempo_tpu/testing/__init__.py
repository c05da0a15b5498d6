"""Test-support subpackage: fault injection
(:mod:`tempo_tpu.testing.faults`) and the chaos campaign harness
(:mod:`tempo_tpu.testing.chaos` — scripted kill/flaky/delay schedules
against live serving + query planes).

Shipped inside the library (not under tests/) so downstream users can
chaos-test their own pipelines against the same harness the ``chaos``
suite uses.  ``chaos`` is imported lazily by its consumers (it pulls
the serve/service planes in); ``faults`` stays import-light.
"""

from tempo_tpu.testing import faults  # noqa: F401

__all__ = ["faults", "chaos"]
