"""Host time per tick of the scheduler: the harness's clock around each
``tick()`` less the batcher's own prefill and decode device-call times
(``prefill_s``, ``decode_s``), over the window's ticks, in ms."""


def read(run):
    w = run.window
    if not w["ticks"]:
        return None
    host = w["tick_wall_s"] - w["prefill_s"] - w["decode_s"]
    return 1e3 * host / w["ticks"]
