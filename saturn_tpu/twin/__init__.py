"""saturn-twin: a deterministic discrete-event simulator for the control
plane.

The twin runs the **real** production code — ``solver/anytime.py``,
``service/admission.py``, ``resilience/replan.py``, the gateway's
shedding/dedup path — against *virtual* slices: chip counts, HBM and
failure processes are parameters, shardflow/memlens-style static priors
stand in for execution as the cost/memory oracle, and a
:class:`~saturn_tpu.twin.engine.VirtualEngine` satisfies the engine
surface by advancing a simulated clock instead of running training steps.

Modules:

- ``clock``    — virtual time (``time.*`` patch) + deterministic event queue
- ``arrivals`` — seeded Poisson + diurnal-burst arrival synthesis (the
  one generator: the live-service fidelity test in ``tests/test_twin.py``
  draws its trace from it too)
- ``fleet``    — virtual devices/slices and seeded per-slice failure
  schedules
- ``oracle``   — static cost/memory model: prior-built strategies, no chips
- ``engine``   — the VirtualEngine dispatch surface (re-exports the real
  forecast arithmetic)
- ``trace``    — journal → arrival trace loading + fidelity comparison
- ``runner``   — the campaign loop mirroring ``SaturnService._run_loop``

Entry point: ``python -m saturn_tpu.analysis twin`` (campaign CLI view).
"""

from saturn_tpu.twin.arrivals import Arrival, arrival_stream  # noqa: F401
from saturn_tpu.twin.clock import EventQueue, VirtualClock  # noqa: F401
from saturn_tpu.twin.fleet import SliceSpec, VirtualFleet  # noqa: F401
