"""One server per device: ServeEngine places server i on local device
i % n — its parameter copy, its pools, its staging transfers and its
compiled cells — and tokens stay bit-identical to one server, through a
live migration between devices.  Subprocess: needs 4 host devices."""

import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import threading
    import numpy as np
    import jax

    from repro.configs.registry import get_config
    from repro.models import model as M
    from repro.serving.engine import ServeEngine, StreamSpec

    devices = jax.local_devices()
    assert len(devices) == 4
    cfg = get_config("internlm2_1_8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    names = [f"s{i}" for i in range(4)]
    prompts = {n: np.arange(1, 6 + i, dtype=np.int32)[None]
               for i, n in enumerate(names)}

    def spec(name, prio):
        return StreamSpec(name, priority=prio, period_ms=1000.0,
                          deadline_ms=1000.0, prefill_ms=5.0, decode_ms=1.0,
                          decode_steps=6)

    def engine(num_servers):
        return ServeEngine(cfg, params, max_seq=32, num_servers=num_servers,
                           batching=True, paged=True, max_batch=4,
                           kv_block_size=8)

    def pool_devices(eng, si):
        return {d for leaf in jax.tree.leaves(eng._paged[si].pools)
                for d in leaf.devices()}

    def run(num_servers, migrate):
        eng = engine(num_servers)
        try:
            rep = eng.precompile((8,))
            for i, n in enumerate(names):
                assert eng.admit(spec(n, i + 1)).admitted
            if migrate:
                src = eng.pool.server_of("s0")
                dst = (src + 1) % num_servers
                assert eng.device_of(src) != eng.device_of(dst)
                assert eng.admission.migrate("s0", dst)[1] == dst
                assert eng.pool.request_migration("s0", dst)
            out = {}

            def work(n):
                out[n] = eng.generate(n, prompts[n], steps=6).tokens

            threads = [threading.Thread(target=work, args=(n,))
                       for n in names]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            assert not any(t.is_alive() for t in threads)
            for si in range(num_servers):
                assert eng.device_of(si) == devices[si], si
                assert pool_devices(eng, si) == {devices[si]}, si
            assert set(eng._placed) == set(devices[:num_servers])
            assert eng.migrations_completed == (1 if migrate else 0)
            assert not any(eng.kv_usage().values()), eng.kv_usage()
            return out, rep
        finally:
            eng.close()

    one, rep1 = run(1, migrate=False)
    four, rep4 = run(4, migrate=True)
    assert one == four, (one, four)
    # compiled cells are per device: every device compiles the plan
    assert rep4.compiled == 4 * rep1.compiled, (rep1, rep4)
    assert rep4.decode_cells == rep1.decode_cells
    print("PLACEMENT-OK")

    # an elastic server on a device no server used yet compiles the warm
    # cells there first: its traffic runs no cold cell
    eng = engine(2)
    try:
        eng.precompile((8,))
        si = eng.add_server()
        assert eng.device_of(si) == devices[2]
        assert eng._warm_of(si).decode == eng._warm_of(0).decode
        assert pool_devices(eng, si) == {devices[2]}
        assert eng.admit(spec("late", 1)).admitted
        dev = eng.admission.device_of("late")
        if dev != si:
            assert eng.admission.migrate("late", si)[1] == si
        eng.pool.reassign("late", si, priority=1)
        tokens = eng.generate("late", prompts["s0"], steps=6).tokens
        assert tokens == one["s0"]
        metas = eng.pool.servers[si].stats.batch_meta
        assert metas and not any(m.get("cold") for m in metas)
    finally:
        eng.close()
    print("ELASTIC-OK")
""")


def test_servers_on_their_own_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "PLACEMENT-OK" in res.stdout
    assert "ELASTIC-OK" in res.stdout
