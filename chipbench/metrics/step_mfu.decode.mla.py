"""Whole step, in the latent-attention cell: ``step_mfu.decode`` read
unchanged (the chip's least time for a decode step's needed work, whose
dense part is the family's ``dense_work``, over the host-clock time of
``decode_batch``, in %)."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "chipbench_metric_step_mfu_decode",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "step_mfu.decode.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)


def read(run):
    return _base.read(run)
