import pytest


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch, tmp_path):
    # the harness turns JAX's persistent compile cache on unless this is
    # set; a test must not leave one in the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
