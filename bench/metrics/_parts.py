"""Device time of one part of a client model, read from the tag the
program puts on each op of the part, shared by the part metrics.

The program tags the ops of each part of a decoder client with the
frontend attribute ``fedhen_part="<part>"`` (self_attn, cross_attn, ffn,
heads), forward and backward alike, and the TPU profiler prints it in
the op's event name.  The text is spelled here, not imported from the
program: a part renamed in the program then reads nothing, and the run
fails by name instead of losing the metric."""

from bench import trace


def tagged(part: str):
    """Predicate on an op's text: the op carries ``part``'s tag."""
    tag = f'fedhen_part="{part}"'
    return lambda op: tag in op


def part_ms(ctx, part: str):
    """Device milliseconds per round of the ops tagged ``part`` (self
    times), or ``None`` when no op carries the tag."""
    s = trace.kernel_seconds(ctx.trace["ops"], tagged(part))
    return 1e3 * s / ctx.rounds if s > 0 else None
