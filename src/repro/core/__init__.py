"""Origami core: blinding, Slalom protocol, precompute, executor, trust,
partition planner.

Import the submodules directly (``from repro.core import plan as PL``,
``from repro.core.origami import OrigamiExecutor``). The package itself
imports nothing, so the kernel wrappers can import ``repro.core.tracing``
without pulling in ``blinding.py``, which imports them back."""
