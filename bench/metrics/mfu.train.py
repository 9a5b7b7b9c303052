"""Fine-tuning model step: FLOPs the trained tokens of the traced window
require, over the window times the chip's bf16 peak, in %.

What a trained token requires is the model family's count
(``train_flops`` in ``bench/arch/<arch>.py``): the forward pass and the
backward pass to the activations (the frozen weights need no gradient),
causal attention forward and backward, and three times the adapter's
forward work. Recomputation (remat) does not count."""
from bench import harness


def read(view):
    if not getattr(view, "trace", None) or "tr0" not in view.snaps \
            or "tr1" not in view.snaps:
        return None
    (ta, i0), (tb, i1) = view.snaps["tr0"], view.snaps["tr1"]
    steps = i1 - i0
    if steps <= 0:
        return None
    mix = view.mix
    fam = harness.family(view.cfg, getattr(view, "family", None))
    per_step = sum(fam.train_flops(view, j, mix["seq"]) * mix["rows"]
                   * mix["seq"] for j in mix["jobs"])
    return 100.0 * steps * per_step / ((tb - ta)
                                       * view.peaks["bf16_flops_per_s"])
