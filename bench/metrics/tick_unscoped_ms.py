"""tick_unscoped_ms: device time per scan tick of the ops in none of the seven
named stages, in ms: copies XLA inserts, the loop's control, the tick's
outputs, and the `afl.guards` and `afl.resync` stages where a build has them.
Device-0 self time over the traced window, over the window's ticks
(`bench/tick_stages.py`)."""
import tick_stages


def read(record):
    return tick_stages.unscoped_tick_ms(record)
