"""Device resolver, compile-cache placement and the chip-only entry points
(util/device.py, chip_smoke.py) as seen from a CPU host: the
portable paths resolve, every demand-a-chip call fails and names what it
found, and nothing writes a number.
"""

import os
import subprocess
import sys

import pytest

from sphexa_tpu.util import device

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_script, *, cwd=ROOT, env_extra=None, script=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra or {})
    cmd = [sys.executable, code_or_script] if script \
        else [sys.executable, "-c", code_or_script]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


class TestResolver:
    def test_cpu_resolves_portable_paths(self):
        from sphexa_tpu.sph.pallas_pairs import pallas_interpret

        info = device.device_info()
        assert info.platform == "cpu" and info.count >= 1 and info.kind
        assert not device.on_tpu()
        assert device.resolve_backend("auto") == "xla"
        assert device.resolve_backend("pallas") == "pallas"
        assert pallas_interpret() is True

    def test_demand_a_chip_raises_naming_the_platform(self):
        with pytest.raises(RuntimeError, match="platform='cpu'"):
            device.require_tpu("this test")


_CACHE_PROBE = """
import jax
from sphexa_tpu.util import device
{patch}
print(repr(device.enable_compile_cache()))
print(repr(jax.config.jax_compilation_cache_dir))
print(jax.config.jax_compilation_cache_include_metadata_in_key)
"""


class TestCompileCache:
    def test_env_set_leaves_config_untouched(self, tmp_path):
        r = _run(_CACHE_PROBE.format(patch="device.on_tpu = lambda: True"),
                 env_extra={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        helper, config, metadata_in_key = r.stdout.strip().splitlines()
        # jax itself read the variable; the helper placed no other
        assert helper == config == repr(str(tmp_path)), r.stderr[-2000:]
        assert metadata_in_key == "True"

    def test_unset_places_checkout_cache_from_any_cwd(self, tmp_path):
        want = repr(os.path.join(ROOT, ".jax_cache"))
        for cwd in (ROOT, str(tmp_path)):
            r = _run(_CACHE_PROBE.format(
                patch="device.on_tpu = lambda: True"), cwd=cwd)
            assert r.stdout.strip().splitlines() == [want, want, "True"], \
                r.stderr[-2000:]

    def test_unset_off_tpu_places_nothing(self, monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        # (the tier's own per-run cache, conftest.pytest_configure, is what
        # the config names before the call; the helper must leave it be)
        before = jax.config.jax_compilation_cache_dir
        assert device.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before

    def test_scope_names_are_part_of_the_cache_key(self):
        """Two programs that differ in a scope name alone get two keys
        under the setting the helper makes, one key under jax's default
        (a capture would then read an older checkout's names on a new
        checkout's ops)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax._src import cache_key, compiler, config

        def key_of(scope):
            def f(x):
                with jax.named_scope(scope):
                    return jnp.sin(x)

            return cache_key.get(
                jax.jit(f).lower(jnp.ones(4)).compiler_ir(),
                np.array(jax.devices()[:1]),
                compiler.get_compile_options(1, 1), jax.devices()[0].client)

        scopes = ("sphexa/neighbors", "sphexa/neighbors~windows",
                  "sphexa/neighbors")
        a, b, a2 = [key_of(s) for s in scopes]
        assert a == b == a2
        with config.compilation_cache_include_metadata_in_key(True):
            a, b, a2 = [key_of(s) for s in scopes]
        assert a == a2 and a != b


class TestChipOnlyEntryPoints:
    def test_refuses_without_a_chip(self, tmp_path):
        """Exits non-zero before compiling anything, names the platform
        it found, prints no result line."""
        r = _run(os.path.join(ROOT, "chip_smoke.py"), cwd=str(tmp_path),
                 script=True)
        assert r.returncode != 0
        assert "platform='cpu'" in r.stderr
        assert '"ok"' not in r.stdout and '"value"' not in r.stdout
        assert not os.listdir(tmp_path)  # no leg ran, nothing written
