"""Device time of one stage of the round, read from the tag the program
puts on each op of the stage, shared by the stage metrics.

The program tags every HLO op of a stage with the frontend attribute
``fedhen_scope="<stage>"`` (local_sgd, wire, fold, finalize), and the TPU
profiler prints it in the op's event name.  The text is spelled here, not
imported from the program: a stage renamed in the program then reads
nothing, and the run fails by name instead of losing the metric."""

from bench import trace


def tagged(stage: str):
    """Predicate on an op's text: the op carries ``stage``'s tag."""
    tag = f'fedhen_scope="{stage}"'
    return lambda op: tag in op


def stage_ms(ctx, stage: str):
    """Device milliseconds per round of the ops tagged ``stage`` (self
    times, so a loop's body is not counted twice), or ``None`` when no op
    carries the tag."""
    s = trace.kernel_seconds(ctx.trace["ops"], tagged(stage))
    return 1e3 * s / ctx.rounds if s > 0 else None
