"""Whole-step utilisation of the chip's bf16 peak, in %: the operations the
window's prompts and generated tokens require (the configuration's counts
module ``bench/counts/<reference>.py``; for GPT-2 the LM head at the last
prompt position only, attention over the positions held) over the
window's host seconds times the peak."""


def read(run):
    if getattr(run, "required_flops", None) is None:
        return None
    if run.host_window_s <= 0:
        return None
    return 100.0 * run.required_flops / (run.host_window_s
                                         * run.peaks["bf16_flops"])
