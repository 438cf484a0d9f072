"""Which ops of a TPU trace are the masked_agg fold kernels, shared by the
fold metrics."""


def is_fold(op: str) -> bool:
    """A masked_agg accumulating fold: a Mosaic kernel whose result is the
    f32[1, N] running sum.  (The kernels carry no stable name yet; in these
    cells they are the round's only Mosaic kernels.)"""
    return ('custom_call_target="tpu_custom_call"' in op
            and op.split(" = ", 1)[-1].startswith("f32[1,"))
