"""The program's spans, on the profiler's clock.

Each span is a :class:`jax.profiler.TraceAnnotation`: it records nothing
unless a profiler trace is running (``jax.profiler.start_trace``), and
costs about a microsecond and a half when none is.  So the spans are
always in place, with no switch.  A trace reader finds them on the host
planes by name, and their arguments among the event's stats.

* ``repro.submit`` — ``MultiModelEngine.submit``; ``rid``, ``tenant``,
  ``tokens`` (the request's tokens: a language-model stage's sequence
  length, 0 for a model that takes none).
* ``repro.step`` — ``MultiModelEngine.step``, round composition
  included; ``active`` (the tenants with queued work).
* ``repro.wave`` — each wave of a step (``_dispatch_wave``); ``rids``
  (the requests it served), ``tokens`` (theirs, summed), ``occupancy``,
  ``analytic_us``.
* ``repro.plan`` — the wave's plan lookup (``_resolve_plan``); ``hit``.
* ``repro.execute`` — the executor call of a wave (one per
  ``execute_plan`` on a floor round), one dispatch of the plan's jitted
  program; ``requests``, ``built`` (true when this call traced and
  compiled, or loaded, that program: once per plan), ``folded`` (the
  kernel nodes that program's trace folded,
  ``PlanPrograms.segments_folded`` counted over the call; 0 when the
  call built nothing).

They nest ``step ⊃ wave ⊃ {plan, execute}`` on the thread that steps the
engine.  A list of ids (``rids``, ``active``) is joined with :data:`SEP`:
the profiler's argument encoding takes ``,``, ``=`` and ``#`` for its
own.  ``analytic_us`` is the wave's cost at the SoC clock: the schedule
model's prediction beside the measured span.

``repro.kernel`` is no host span: a plan runs as one jitted program
(``repro.core.runtime.PlanPrograms``), so each kernel node's ops sit in a
``jax.named_scope`` named ``repro.kernel:<tenant>:<supernode>`` inside
it (``runtime.kernel_scope``), ``tenant`` being the model's graph name
(the same in every plan the model runs in).  The scope is in the op
metadata (``op_name``) of the compiled program's instructions, where a
trace reader joins it to the device ops by instruction name; a fusion
carries the scope of one of the ops it fused.  The first kernel node
that covers an op computes it whole, so a folded node, a tile segment
of ops that earlier nodes computed, names no ops and its scope appears
nowhere.  Inside a kernel's scope the ops of a sequence-model mechanism
sit in one more, ``repro.op:<op type>`` (``ssd``, ``causal_conv1d``,
``softmax`` when causal; ``runtime.op_scope``).
"""

from __future__ import annotations

from typing import Iterable

from jax.profiler import TraceAnnotation

SUBMIT = "repro.submit"
STEP = "repro.step"
WAVE = "repro.wave"
PLAN = "repro.plan"
EXECUTE = "repro.execute"
KERNEL = "repro.kernel"
OP = "repro.op"

SEP = ";"

# ``span(name, **args)`` as a context manager; ``set_metadata(**more)`` on
# it adds arguments known only once its work is done.
span = TraceAnnotation


def joined(values: Iterable[int]) -> str:
    """``values`` joined into one span argument."""
    return SEP.join(str(v) for v in values)
