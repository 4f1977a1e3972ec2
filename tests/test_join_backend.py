"""The platform picks the join backend (``repro.core.join.resolve_backend``).

On this CPU the serving entry points resolve to the pure-jnp reference,
an explicit ``PALLAS`` (compiled TPU kernels) is refused instead of
silently falling back, and a restore re-resolves the backend instead of
trusting the one a checkpoint manifest carries.
"""

import glob
import json
import os

import jax
import pytest

from repro.api import StreamSession
from repro.core import compile_plan
from repro.core.join import JoinBackend, resolve_backend
from repro.core.multi import SlotTickCache
from repro.core.share import SharedPrefixForest
from repro.launch.stream_serve import StreamServer
from repro.runtime.mesh import ShardedSearchService
from repro.runtime.service import ContinuousSearchService

from test_engine_oracle import small_stream
from test_service_restore import CAP, SERVE, chain_query

ON_CPU = jax.devices()[0].platform == "cpu"


@pytest.mark.skipif(not ON_CPU, reason="asserts the CPU platform's choice")
def test_default_backend_resolves_to_ref_on_cpu():
    assert resolve_backend() == JoinBackend.REF
    assert ContinuousSearchService(**CAP).backend == JoinBackend.REF
    assert StreamSession(**CAP).service.backend == JoinBackend.REF
    assert ShardedSearchService(n_replicas=1, **CAP).backend \
        == JoinBackend.REF
    assert SharedPrefixForest(SlotTickCache()).backend == JoinBackend.REF
    plan = compile_plan(chain_query(), 20, level_capacity=512,
                        l0_capacity=512, max_new=256)
    assert StreamServer(plan).session.service.backend == JoinBackend.REF


@pytest.mark.skipif(not ON_CPU, reason="PALLAS is legal on a TPU")
def test_explicit_pallas_off_tpu_raises():
    with pytest.raises(ValueError, match="needs a TPU"):
        resolve_backend(JoinBackend.PALLAS)
    with pytest.raises(ValueError, match="needs a TPU"):
        ContinuousSearchService(backend=JoinBackend.PALLAS, **CAP)
    with pytest.raises(ValueError, match="needs a TPU"):
        StreamSession(backend=JoinBackend.PALLAS, **CAP)


@pytest.mark.parametrize(
    "backend", [JoinBackend.REF, JoinBackend.PALLAS_INTERPRET])
def test_explicit_test_backends_are_honored(backend):
    assert resolve_backend(backend) == backend
    assert ContinuousSearchService(backend=backend, **CAP).backend == backend
    with pytest.raises(ValueError, match="unknown join backend"):
        resolve_backend("cuda")


def test_restore_resolves_backend_by_platform(tmp_path):
    """A checkpoint whose manifest names ``"pallas"`` (written where the
    kernels ran, before the backend left the manifest) restores with the
    restoring platform's backend: no error here, and no REF on a chip."""
    svc = ContinuousSearchService(slots_per_group=2, ckpt_dir=str(tmp_path),
                                  **CAP)
    svc.register(chain_query(), 20)
    svc.serve_stream(small_stream(64, n_vertices=9, seed=48),
                     ckpt_every=2, **SERVE)
    manifests = glob.glob(os.path.join(str(tmp_path), "step_*.json"))
    assert manifests
    for path in manifests:
        with open(path) as f:
            man = json.load(f)
        assert "backend" not in man["service"]["config"]
        man["service"]["config"]["backend"] = JoinBackend.PALLAS
        with open(path, "w") as f:
            json.dump(man, f)
    restored = ContinuousSearchService.restore(str(tmp_path))
    assert restored.backend == resolve_backend()
    assert restored.registry.qids() == svc.registry.qids()
