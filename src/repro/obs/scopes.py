"""The jax side of observability: stage tags inside the compiled round,
and the count of backend compiles that telemetry spans carry.

**Stages.**  The round is one jit, so a host span cannot time its parts.
:func:`stage` tags every op traced inside it twice: ``jax.named_scope``
puts the stage in the ops' ``op_name`` (HLO dumps, profiler UIs), and
``set_xla_metadata`` puts the frontend attribute
``fedhen_scope="<stage>"`` on the HLO instruction, which the TPU
profiler prints in each XLA op's event name.  A reader of a device trace
sums op times by that text.  The stages (:data:`STAGES`):

* ``local_sgd`` — the chunk's client trainers, one after another
  (forward, backward, the optimizer step), SCAFFOLD's gradient
  correction and the NaN-device finiteness test;
* ``wire`` — the broadcast's encode/decode trip (the async engine's
  version decode) and the wire-v2 upload's pack, encode, decode and
  error-feedback residual;
* ``fold`` — the aggregation state's init and every chunk fold (the
  ``masked_agg`` kernels with their packing and casts);
* ``finalize`` — normalizing the sums into the new server model, the
  SCAFFOLD server control-variate update, the async version publish.

Stages never nest, so no op carries two.  The loop machinery of the
chunk scan (counters, slices of the scanned inputs, copies) stays
untagged.  The tags are metadata: the compiled program is the same,
instruction for instruction, as without them, and they are there with
telemetry on or off.

**Parts.**  Inside a stage, :func:`part` tags the ops of one part of a
client model with a second attribute, ``fedhen_part="<part>"``, set in
``models/``.  The parts (:data:`PARTS`):

* ``self_attn`` — an attention mixer with its pre-norm;
* ``cross_attn`` — the cross-attention sub-block with its norm, and the
  projection of the conditioning that feeds it;
* ``ffn`` — the feed-forward (dense or MoE) sub-block with its norm;
* ``heads`` — the output norm, the output heads and the loss over them.

The attribute is set where the forward pass is traced; JAX's
differentiation transposes each op inside the same metadata, so the
backward pass's ops carry the part of the forward op they come from.
Parts never nest, and like stages they change no instruction.

**Compiles.**  :func:`compile_count` is the process's count of XLA
backend compiles (JAX's ``/jax/core/compile/backend_compile_duration``
monitoring event), which an enabled telemetry span reads at entry and
exit; ``obs/telemetry.py`` imports it only once jax is loaded, so its own
import stays jax-free.  A load from the persistent compilation cache is
not a backend compile.
"""

from __future__ import annotations

import contextlib

import jax
from jax.experimental.xla_metadata import set_xla_metadata

STAGES = ("local_sgd", "wire", "fold", "finalize")
PARTS = ("self_attn", "cross_attn", "ffn", "heads")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def stage(name: str):
    """Tag every op traced inside with the round stage ``name``."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages are {STAGES}")
    with jax.named_scope(name), set_xla_metadata(fedhen_scope=name):
        yield


@contextlib.contextmanager
def part(name: str):
    """Tag every op traced inside with the client-model part ``name``."""
    if name not in PARTS:
        raise ValueError(f"unknown part {name!r}; parts are {PARTS}")
    with set_xla_metadata(fedhen_part=name):
        yield


class _CompileCounter:
    """Backend compiles seen since the listener went in; read as a
    monotonic count whose differences bracket a span."""

    def __init__(self):
        self.count = 0
        self.listening = False

    def __call__(self, event: str, duration_s: float, **kwargs) -> None:
        if event == _BACKEND_COMPILE:
            self.count += 1

    def read(self) -> int:
        if not self.listening:
            jax.monitoring.register_event_duration_secs_listener(self)
            self.listening = True
        return self.count


_COMPILES = _CompileCounter()


def compile_count() -> int:
    """Backend compiles in this process so far (counting starts at the
    first call: JAX's monitoring events are process-wide)."""
    return _COMPILES.read()
