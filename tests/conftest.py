"""Shared tiny-model fixtures. Tests run on the plain 1-device CPU backend —
the 512-device dry-run is exercised only via repro.launch.dryrun."""
import gc
import re

import numpy as np
import pytest

import jax

from repro.configs.base import (ATTN, RECURRENT, FrontendConfig, MLAConfig,
                                ModelConfig, MoEConfig, RecurrentConfig,
                                SSMConfig)

try:                      # property-based modules importorskip hypothesis
    from hypothesis import settings
except ImportError:       # suite must still collect without it
    settings = None

if settings is not None:
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")


def tiny(name, **kw) -> ModelConfig:
    base = dict(name=name, family="dense", num_layers=3, d_model=64,
                num_heads=4, num_kv_heads=4, head_dim=16, d_ff=128,
                vocab_size=128, param_dtype="float32",
                compute_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


TINY_FAMILIES = {
    "dense": tiny("dense"),
    "dense-bias-qknorm": tiny("dense-bias-qknorm", qkv_bias=True,
                              qk_norm=True, num_kv_heads=2),
    "sliding": tiny("sliding", attention_kind="sliding", sliding_window=8),
    "mla": tiny("mla", attention_kind="mla",
                mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                              qk_rope_head_dim=8, v_head_dim=16)),
    "moe": tiny("moe", family="moe",
                moe=MoEConfig(num_experts=4, num_shared_experts=1, top_k=2,
                              d_ff_expert=32, first_dense_layers=1)),
    "hybrid": tiny("hybrid", family="hybrid", attention_kind="sliding",
                   sliding_window=8, num_layers=5,
                   recurrent=RecurrentConfig(
                       lru_width=64, d_conv=4,
                       block_pattern=(RECURRENT, RECURRENT, ATTN))),
    "ssm": tiny("ssm", family="ssm", attention_kind="none", num_kv_heads=0,
                d_ff=0, num_heads=8,
                ssm=SSMConfig(d_state=16, head_dim=16, expand=2, d_conv=4,
                              chunk_size=4)),
    "encdec": tiny("encdec", family="audio", encoder_layers=2,
                   frontend=FrontendConfig(kind="audio")),
    "vlm": tiny("vlm", family="vlm", num_kv_heads=2,
                frontend=FrontendConfig(kind="vision", num_patches=4)),
}


def hlo_without_metadata(hlo: str) -> str:
    """Compiled HLO text without op metadata and the source tables it
    points to: what the program computes, not where it came from."""
    lines = [line for line in hlo.splitlines() if not re.match(
        r"(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)", line)]
    return re.sub(r",? metadata=\{[^}]*\}", "", "\n".join(lines))


@pytest.fixture(scope="module", autouse=True)
def _no_programs_from_earlier_modules():
    """Each test module starts with no compiled program that an earlier
    module left alive. A profiler trace lists every live program, so what
    a module reads from a trace must not depend on which modules ran
    before it in the same worker."""
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(params=sorted(TINY_FAMILIES))
def family_cfg(request):
    return TINY_FAMILIES[request.param]
