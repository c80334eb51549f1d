"""The model step's share of the chip's peak in a closed-loop batch job:
model FLOPs of the prefill and decode steps that ran in the traced window,
over the device time of those steps times the chip's bf16 peak.

It is read as ``mfu.serve`` reads it: the traced run serves one
calibration batch of each shape just before its window, inside a
``cb:calibrate:<prompt>:<new>`` span, which names the compiled prefill and
decode steps (XLA module events) of that shape; FLOPs per step are from
``counts/<config>.py``, a decode step counted at the mean context of its
request shape."""
from pathlib import Path

from chipbench.bench import load_module

_SERVE = load_module(Path(__file__).with_name("mfu.serve.py"))


def read(run):
    return _SERVE.read(run)
