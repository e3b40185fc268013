"""tick_update_ms: device time per scan tick of the model update: the learning
rate and the step of the model (the `afl.update` stage), in ms: the stage's
self time on device 0 over the traced window, over the window's ticks. The
stage of each op is read from the compiled chunk (`bench/tick_stages.py`)."""
import tick_stages


def read(record):
    return tick_stages.tick_ms(record, "afl.update")
