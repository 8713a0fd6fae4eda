"""Model FLOPs of the window's requests (unpadded prompt tokens and the
counted generated tokens, from the reference's per-family FLOP count)
over the window's length, the chips and the chip's bf16 peak, in %.
Padding and tokens decoded past a request's end do not count."""


def read(run):
    if not run.window_s or not run.model_flops:
        return None
    return 100.0 * run.model_flops / (run.window_s * run.chips
                                      * run.peak_flops)
