"""Share of the K/V pool's held tokens that NO WINDOW layer reads any more: over
the window's decode launches (flight ring), the cached tokens of the live
streams that lie behind the attention window (``attended_tokens`` less
``window_tokens``: each stream's context less its context cut to the window,
summed over steps and streams) over all their cached tokens, times the
window layers' share of the pool's layers (a full layer reads every token).
What per-kind block tables, which give a window layer's blocks back once they
fall out of the window, would free: 0 while every context is shorter than the
window, towards the window layers' share of the pool as contexts grow. The
program's gauge ``localai_kv_window_dead_tokens`` is the same count in whole
blocks at one moment. None where the ring has no ``window_tokens`` column or
it reads 0 (a model with no window layer, the parent)."""

from harness import layerlib as ll


def read(ctx):
    w = ctx["window"]
    cell = ctx["cell"]
    rows = [r for r in ll.flight(ctx, w.t_open, w.t_close,
                                 ("decode", "decode_n"))
            if r.get("window_tokens")]
    attended = sum(r["attended_tokens"] for r in rows)
    if not attended or not hasattr(cell.family, "window_bytes"):
        return None
    dead = attended - sum(r["window_tokens"] for r in rows)
    n = cell.family.dims(cell.published)
    return 100.0 * dead / attended * n["windowed"] / n["L"]
