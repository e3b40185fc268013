"""tick_sample_ms: device time per scan tick of the sampling stage: the
availability mask, the sampling logits, the argmax / top-k draw of the tick's
clients and the staleness clamp (the `afl.sample` stage), in ms: the stage's
self time on device 0 over the traced window, over the window's ticks. The
stage of each op is read from the compiled chunk (`bench/tick_stages.py`)."""
import tick_stages


def read(record):
    return tick_stages.tick_ms(record, "afl.sample")
