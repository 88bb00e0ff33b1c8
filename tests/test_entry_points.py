"""Entry-point plumbing: which model ``serving.build`` builds, the
``serve.py --layers`` flag, and where the persistent compile cache lives."""
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_reduced
from repro.core.scheduler import Request
from repro.launch import compile_cache
from repro.launch.serve import build_arg_parser
from repro.serving.factory import build, cached_params

ROOT = Path(__file__).resolve().parents[1]


def test_build_takes_a_model_config():
    """A ModelConfig is built exactly as given (an arch id still builds its
    reduced preset), with params cached per (config, seed)."""
    cfg = get_reduced("qwen2.5-32b").replace(n_layers=1)
    assert cached_params(cfg, 0) is cached_params(cfg, 0)
    eng = build(cfg, batch=2, max_seq=32, block_tokens=8)[0]
    assert eng.cfg == cfg
    assert eng.params["layers"]["ln1"]["scale"].shape[0] == 1
    assert build("qwen2.5-32b", batch=2, max_seq=32, block_tokens=8
                 )[0].cfg == get_reduced("qwen2.5-32b")
    req = Request(rid=0, prompt=np.arange(5, dtype=np.int32), gen_len=3)
    eng.submit(req)
    eng.run()
    assert len(req.generated) == 3


def test_serve_layers_flag():
    ap = build_arg_parser()
    assert ap.parse_args([]).layers == 0
    assert ap.parse_args(["--layers", "4"]).layers == 4


def test_compile_cache_dir(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the cache; otherwise it sits at
    <repo>/.jax_cache, which git ignores."""
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(ROOT / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
