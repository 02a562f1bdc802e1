"""Utility-type semantics: VectorClock partial order + DenseNatMap density.

Oracle behaviors from the reference's inline tests
(``/root/reference/src/util/vector_clock.rs``, ``src/util/densenatmap.rs``).
"""

import pytest

from stateright_tpu.actor import Id
from stateright_tpu.core.fingerprint import fingerprint, stable_hash
from stateright_tpu.utils import DenseNatMap, RewritePlan, VectorClock


class TestVectorClock:
    def test_incremented_grows(self):
        vc = VectorClock().incremented(2)
        assert vc.elems() == (0, 0, 1)
        assert vc.incremented(0).elems() == (1, 0, 1)

    def test_merge_max(self):
        a = VectorClock([1, 5, 0])
        b = VectorClock([2, 3])
        assert VectorClock.merge_max(a, b) == VectorClock([2, 5, 0])

    def test_equality_pads_implicit_zeros(self):
        assert VectorClock([1, 0]) == VectorClock([1])
        assert VectorClock([1, 0]) != VectorClock([1, 1])

    def test_hash_truncates_trailing_zeros(self):
        assert hash(VectorClock([1, 0])) == hash(VectorClock([1]))
        assert stable_hash(VectorClock([1, 0, 0])) == stable_hash(
            VectorClock([1])
        )
        assert fingerprint(VectorClock([2, 1, 0])) == fingerprint(
            VectorClock([2, 1])
        )

    def test_partial_order(self):
        assert VectorClock([1, 2]) < VectorClock([2, 2])
        assert VectorClock([1, 2]) <= VectorClock([1, 2])
        assert VectorClock([2, 2]) > VectorClock([1, 2])
        assert VectorClock([1, 2, 0]) >= VectorClock([1, 2])

    def test_concurrent_clocks_incomparable(self):
        a, b = VectorClock([1, 0]), VectorClock([0, 1])
        assert a.concurrent_with(b)
        assert not (a < b) and not (a > b)
        assert not (a <= b) and not (a >= b)

    def test_display(self):
        assert str(VectorClock([1, 2])) == "<1, 2, ...>"


class TestDenseNatMap:
    def test_insert_appends_and_overwrites(self):
        m = DenseNatMap()
        assert m.insert(Id(0), "a") is None
        assert m.insert(Id(1), "b") is None
        assert m.insert(Id(0), "c") == "a"
        assert list(m) == ["c", "b"]

    def test_out_of_order_insert_raises(self):
        m = DenseNatMap()
        with pytest.raises(IndexError):
            m.insert(Id(1), "x")

    def test_from_pairs_any_order(self):
        m = DenseNatMap.from_pairs([(Id(1), "b"), (Id(0), "a")])
        assert m.values() == ["a", "b"]
        assert m.items() == [(Id(0), "a"), (Id(1), "b")]

    def test_from_pairs_rejects_sparse(self):
        with pytest.raises(ValueError):
            DenseNatMap.from_pairs([(Id(0), "a"), (Id(2), "c")])
        with pytest.raises(ValueError):
            DenseNatMap.from_pairs([(Id(0), "a"), (Id(0), "b")])

    def test_rewrite_reindexes(self):
        m = DenseNatMap(["b", "a"])
        plan = RewritePlan.from_values_to_sort(m.values())
        assert plan.reindex(m.values()) == ["a", "b"]
        rewritten = rewrite_roundtrip(m, plan)
        assert rewritten.values() == ["a", "b"]

    def test_stable_hash_matches_tuple(self):
        m = DenseNatMap(["a", "b"])
        assert fingerprint(m) != 0
        assert m == DenseNatMap(["a", "b"])
        assert m != DenseNatMap(["b", "a"])


def rewrite_roundtrip(value, plan):
    from stateright_tpu.utils import rewrite_value

    return rewrite_value(value, plan)


class TestCompileCache:
    """One cache location, placeable from outside: the environment's
    ``JAX_COMPILATION_CACHE_DIR`` wins, and otherwise the path is fixed
    inside the checkout (the path is part of the cache's key)."""

    _PROBE = (
        "import jax\n"
        "from stateright_tpu.utils.compile_cache import "
        "cache_dir, enable_persistent_cache\n"
        "enable_persistent_cache()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(cache_dir())\n"
    )

    def _probe(self, env_dir):
        import os
        import subprocess
        import sys

        env = dict(os.environ)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "-c", self._PROBE], env=env, cwd=repo,
            capture_output=True, text=True, timeout=120, check=True,
        )
        return out.stdout.split()

    def test_env_var_wins_and_nothing_else_is_set(self, tmp_path):
        d = str(tmp_path / "outside")
        assert self._probe(d) == [d, d]
        # enable_persistent_cache made no directory of its own.
        import os

        assert not os.path.exists(d)

    def test_fixed_checkout_path_stable_across_processes(self):
        import os

        first = self._probe(None)
        second = self._probe(None)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        expected = os.path.join(repo, ".jax_cache")
        assert first == second == [expected, expected]
        assert os.path.isdir(expected)

    def test_cache_dir_in_process(self, monkeypatch):
        import os

        from stateright_tpu.utils.compile_cache import cache_dir

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cache_dir() == "/elsewhere"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cache_dir().endswith(os.sep + ".jax_cache")
