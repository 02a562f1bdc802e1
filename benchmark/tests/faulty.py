"""2pc models whose step is broken on purpose, for test_faults.py."""

import jax.numpy as jnp

from stateright_tpu.models.two_phase_commit import TwoPhaseSys


class StuckStep(TwoPhaseSys):
    """Every action returns the state unchanged."""

    def packed_expand(self, state):
        children, valid = super().packed_expand(state)
        a = self.packed_action_count()
        same = {k: jnp.broadcast_to(v, (a,) + v.shape) for k, v in state.items()}
        return same, valid


class HalfBatch(TwoPhaseSys):
    """Half of every state's candidates are left out."""

    def packed_expand(self, state):
        children, valid = super().packed_expand(state)
        return children, valid & (jnp.arange(valid.shape[0]) % 2 == 0)


class AlteredAnswer(TwoPhaseSys):
    """RM 0's choice to abort produces an RM 0 that committed."""

    CHOOSE_TO_ABORT_RM0 = 4

    def packed_expand(self, state):
        children, valid = super().packed_expand(state)
        rm = children["rm"].at[self.CHOOSE_TO_ABORT_RM0, 0].set(jnp.uint32(2))
        return dict(children, rm=rm), valid
