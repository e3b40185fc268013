"""tick_client_ms: device time per scan tick of the client gradient: the payload
of each arriving client at its stale model (the `afl.client` stage), in ms: the
stage's self time on device 0 over the traced window, over the window's ticks.
The stage of each op is read from the compiled chunk (`bench/tick_stages.py`)."""
import tick_stages


def read(record):
    return tick_stages.tick_ms(record, "afl.client")
