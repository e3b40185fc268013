"""tick_stale_read_ms: device time per scan tick of the stale-model read: the
history-ring rows at the clients' staleness (the int8 ring's dequantize
included) (the `afl.stale_read` stage), in ms: the stage's self time on device
0 over the traced window, over the window's ticks. The stage of each op is read
from the compiled chunk (`bench/tick_stages.py`)."""
import tick_stages


def read(record):
    return tick_stages.tick_ms(record, "afl.stale_read")
