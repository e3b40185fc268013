"""tick_select_ms: device time per scan tick of the state gate: the select that
keeps the aggregator state of a tick with no live arrival (the `afl.select`
stage), in ms: the stage's self time on device 0 over the traced window, over
the window's ticks. The stage of each op is read from the compiled chunk
(`bench/tick_stages.py`)."""
import tick_stages


def read(record):
    return tick_stages.tick_ms(record, "afl.select")
