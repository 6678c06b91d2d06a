"""The builders set ``jax_default_prng_impl`` for the process, as the
command wants it; a test worker goes on to other files, so every test here
hands the setting back as it found it."""

import jax
import pytest


@pytest.fixture(autouse=True)
def _prng_impl_restored():
    old = str(jax.config.jax_default_prng_impl)
    yield
    jax.config.update("jax_default_prng_impl", old)
