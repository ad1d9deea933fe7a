"""Model step: plain VGG forward FLOPs per image x images/s over the chip's
bf16 peak."""
import readers


def read(run):
    return readers.mfu(run, "images")
