"""Factories of the cells that spread one check over several chips.

The harness spawns every check with ``spawn_tpu_bfs(**spawn)``; a
multi-chip cell's ``spawn`` names ``devices``. A program whose
``spawn_tpu_bfs`` takes no ``devices`` cannot run such a cell: the factory
refuses it before set-up, so the run exits non-zero at once instead of
recording every check of its window as an error.
"""

import inspect


def _require_devices():
    from stateright_tpu.checker.builder import CheckerBuilder

    if "devices" not in inspect.signature(CheckerBuilder.spawn_tpu_bfs).parameters:
        raise SystemExit(
            "this program's spawn_tpu_bfs takes no devices: it cannot spread "
            "a check over chips"
        )


def two_phase_commit(rm_count):
    """``TwoPhaseSys(rm_count)``, for a program that can shard its check."""
    _require_devices()
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    return TwoPhaseSys(rm_count)
