"""Model step: the rows of the dense decode cache the attention streams, as
a share of the rows it holds (B x L a step), over every decode step the
replicas' decode pools have run (``PhaseStats.decode_kv_rows_read`` and
``decode_kv_rows_held``). 100 where the XLA path reads every row. It
describes the chip's decode program, so a run off the TPU reads nothing,
and so does a program that keeps no such counter."""
import jax


def read(run):
    if jax.default_backend() != "tpu":
        return None
    read = held = 0
    for r in run.fleet.replicas:
        st = r.stats
        if not hasattr(st, "decode_kv_rows_held"):
            return None
        read += st.decode_kv_rows_read
        held += st.decode_kv_rows_held
    return 100.0 * read / held if held else None
