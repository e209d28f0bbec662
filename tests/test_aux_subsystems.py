"""Aux subsystems: DocModule re-sourcing, file-leak tracking, memory budget.

Reference counterparts: modin/tests/config/test_envvars.py (DocModule),
modin/config/envvars.py:893 (TrackFileLeaks), Memory-bounded spill.
"""

import sys
import types
import warnings

import numpy as np
import pytest

import modin_tpu.pandas as pd
from modin_tpu.config import Memory, TrackFileLeaks


@pytest.fixture
def docs_module():
    mod = types.ModuleType("_test_docs_module")

    class DataFrame:
        """Test-custom frame doc."""

        def sum(self):
            """Test-custom sum doc."""

    mod.DataFrame = DataFrame
    sys.modules["_test_docs_module"] = mod
    yield mod
    sys.modules.pop("_test_docs_module", None)


class TestDocModule:
    def test_docs_resourced_and_restorable(self, docs_module):
        from modin_tpu.config import DocModule

        pandas_frame_doc = pd.DataFrame.__doc__
        pandas_sum_doc = pd.DataFrame.sum.__doc__
        with DocModule.context("_test_docs_module"):
            assert pd.DataFrame.__doc__ == "Test-custom frame doc."
            assert pd.DataFrame.sum.__doc__ == "Test-custom sum doc."
            # no counterpart in the custom module -> pandas doc stays
            assert "Test-custom" not in (pd.DataFrame.mean.__doc__ or "")
            assert "Test-custom" not in (pd.Series.__doc__ or "")
        # leaving the context reverts to "pandas": originals restored
        assert pd.DataFrame.__doc__ == pandas_frame_doc
        assert pd.DataFrame.sum.__doc__ == pandas_sum_doc

    def test_hand_written_docs_never_clobbered(self, docs_module):
        import pandas

        from modin_tpu.config import DocModule
        from modin_tpu.utils import _inherit_docstrings

        @_inherit_docstrings(pandas.DataFrame)
        class MyFrame:
            def sum(self):
                """Hand-written sum doc."""

            def mean(self):
                pass  # doc inherited from pandas at decoration

        assert MyFrame.mean.__doc__ == pandas.DataFrame.mean.__doc__
        with DocModule.context("_test_docs_module"):
            # the custom module HAS a sum counterpart, but MyFrame.sum's doc
            # was hand-written (not written by inheritance) -> untouched
            assert MyFrame.sum.__doc__ == "Hand-written sum doc."
            assert MyFrame.__doc__ == "Test-custom frame doc."
        assert MyFrame.sum.__doc__ == "Hand-written sum doc."
        assert MyFrame.mean.__doc__ == pandas.DataFrame.mean.__doc__

    def test_missing_module_warns_and_keeps_docs(self):
        from modin_tpu.config import DocModule

        doc_before = pd.DataFrame.__doc__
        with pytest.warns(UserWarning, match="not importable"):
            with DocModule.context("_no_such_docs_module_"):
                assert pd.DataFrame.__doc__ == doc_before


class TestTrackFileLeaks:
    def test_leak_detected(self, tmp_path):
        from modin_tpu.utils.file_leaks import track_file_leaks

        p = tmp_path / "leak.txt"
        p.write_text("x")
        with TrackFileLeaks.context(True):
            with pytest.warns(ResourceWarning, match="leak.txt"):
                with track_file_leaks():
                    handle = open(p)  # noqa: SIM115 - leak on purpose
            handle.close()

    def test_clean_read_no_warning(self, tmp_path):
        csv = tmp_path / "clean.csv"
        csv.write_text("a,b\n1,2\n3,4\n")
        with TrackFileLeaks.context(True):
            with warnings.catch_warnings():
                warnings.simplefilter("error", ResourceWarning)
                df = pd.read_csv(csv)
        assert len(df) == 2

    def test_disabled_is_noop(self, tmp_path):
        from modin_tpu.utils.file_leaks import track_file_leaks

        p = tmp_path / "leak2.txt"
        p.write_text("x")
        with TrackFileLeaks.context(False):
            with warnings.catch_warnings():
                warnings.simplefilter("error", ResourceWarning)
                with track_file_leaks():
                    handle = open(p)  # noqa: SIM115
        handle.close()


@pytest.fixture(autouse=True)
def _require_device_columns(request):
    if "TestMemoryBudget" in request.node.nodeid:
        from modin_tpu.utils import get_current_execution

        if get_current_execution() != "TpuOnJax":
            pytest.skip("host-cache ledger exists only for device columns")


class TestMemoryBudget:
    def test_lru_eviction_under_budget(self):
        from modin_tpu.core.memory import host_cache_bytes, ledger

        base = host_cache_bytes()
        big = np.arange(200_000, dtype=np.int64)  # 1.6 MB
        df1 = pd.DataFrame({"a": big})
        df2 = pd.DataFrame({"b": big + 1})
        assert host_cache_bytes() >= base + 2 * big.nbytes
        col1 = df1._query_compiler._modin_frame._columns[0]
        col2 = df2._query_compiler._modin_frame._columns[0]
        # budget fits only one cache above the pre-existing load
        with Memory.context(base + int(1.5 * big.nbytes)):
            ledger.enforce()
        assert col1.host_cache is None  # oldest evicted
        assert col2.host_cache is not None
        # evicted column still reads exactly from device
        np.testing.assert_array_equal(col1.to_numpy(), big)

    def test_touch_refreshes_lru(self):
        from modin_tpu.core.memory import host_cache_bytes, ledger

        base = host_cache_bytes()
        big = np.arange(200_000, dtype=np.int64)
        df1 = pd.DataFrame({"a": big})
        df2 = pd.DataFrame({"b": big + 1})
        col1 = df1._query_compiler._modin_frame._columns[0]
        col2 = df2._query_compiler._modin_frame._columns[0]
        col1.to_numpy()  # touch: col1 becomes most-recently-used
        with Memory.context(base + int(1.5 * big.nbytes)):
            ledger.enforce()
        assert col1.host_cache is not None
        assert col2.host_cache is None

    def test_downcast_cache_never_evicted(self):
        from modin_tpu.config import Float64Policy
        from modin_tpu.core.memory import host_cache_bytes, ledger

        with Float64Policy.context("Downcast"):
            base = host_cache_bytes()
            values = np.linspace(0.0, 1.0, 200_000)  # f64, stored f32 on device
            df = pd.DataFrame({"a": values})
            col = df._query_compiler._modin_frame._columns[0]
            with Memory.context(max(base - 1, 0)):  # force over-budget
                ledger.enforce()
            # the cache is the only exact copy: must survive
            assert col.host_cache is not None
            np.testing.assert_array_equal(col.to_numpy(), values)

    def test_unset_budget_keeps_everything(self):
        from modin_tpu.core.memory import ledger

        df = pd.DataFrame({"a": np.arange(1000)})
        col = df._query_compiler._modin_frame._columns[0]
        ledger.enforce()
        assert col.host_cache is not None


class TestCompilationCachePlacement:
    """The persistent compile cache is placed from outside (engine.py)."""

    @pytest.fixture
    def config_updates(self, monkeypatch):
        import jax

        seen = {}
        monkeypatch.setattr(
            jax.config, "update", lambda name, value: seen.__setitem__(name, value)
        )
        return seen

    def test_env_var_means_no_directory_set_in_code(
        self, monkeypatch, tmp_path, config_updates
    ):
        from modin_tpu.parallel import engine

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
        engine._place_compilation_cache()
        assert "jax_compilation_cache_dir" not in config_updates
        assert config_updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
        assert not (tmp_path / "outside").exists()  # jax makes it, not us

    def test_unset_means_one_fixed_path_in_the_checkout(
        self, monkeypatch, tmp_path, config_updates
    ):
        import os

        from modin_tpu.parallel import engine

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(
            engine, "DEFAULT_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache")
        )
        engine._place_compilation_cache()
        assert config_updates["jax_compilation_cache_dir"] == str(
            tmp_path / "jax_cache"
        )
        assert os.path.isdir(tmp_path / "jax_cache")

    def test_default_path_is_fixed_and_inside_the_checkout(self):
        import os

        import modin_tpu
        from modin_tpu.parallel import engine

        checkout = os.path.dirname(os.path.dirname(os.path.abspath(modin_tpu.__file__)))
        assert engine.DEFAULT_COMPILATION_CACHE_DIR == os.path.join(
            checkout, ".modin_tpu", "jax_cache"
        )

    def test_option_is_gone(self):
        import modin_tpu.config as config

        assert not hasattr(config, "CompilationCacheDir")
