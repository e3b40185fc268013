"""mfu: the whole server tick's share of the chip's peak, in %: the larger of
the tick's required FLOPs over the peak FLOP/s and its required bytes over the
peak HBM bytes/s, times the ticks per second of the traced window, over the
chips. What a tick requires is the configuration family's `tick_work`: model
FLOPs for a client gradient (3 × the forward), the bytes the server state
must move once for the flat server."""


def read(record):
    work = record["family"].tick_work(record["config"], record["traffic"])
    peaks, chips = record["peaks"], record["chips"]
    rate = record["ticks"] / record["window_s"]
    share = max(work["flops"] / peaks["flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"]) * rate / chips
    return 100.0 * share if share > 0 else None
