"""End-to-end multi-server + continuous-batching serving: >=4 admitted
streams over >=2 servers, batched greedy decode must reproduce the
unbatched engine's tokens exactly — for BOTH decode-cache layouts: the
masked-dense slot cache and the paged block-pool layout (slot compaction +
block-table gather + length-bucketed batched prefill)."""

import threading

import numpy as np
import pytest

import jax

from repro.configs.registry import get_config
from repro.models import model as M
from repro.serving.engine import ServeEngine, StreamSpec

STEPS = 6


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("internlm2_1_8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _spec(name, prio, steps=STEPS):
    return StreamSpec(name=name, priority=prio, period_ms=8000.0,
                      deadline_ms=8000.0, prefill_ms=50.0, decode_ms=5.0,
                      decode_steps=steps)


def _reference_tokens(cfg, params, prompt):
    eng = ServeEngine(cfg, params, max_seq=32)
    try:
        assert eng.admit(_spec("ref", 1)).admitted
        return eng.generate("ref", prompt, steps=STEPS).tokens
    finally:
        eng.close()


class TestBatchedPoolServing:
    def test_four_streams_two_servers_match_unbatched(self, setup):
        cfg, params = setup
        prompt = np.array([[1, 2, 3, 4]], np.int32)
        want = _reference_tokens(cfg, params, prompt)
        assert len(want) == STEPS

        eng = ServeEngine(cfg, params, max_seq=32, num_servers=2,
                          batching=True, max_batch=4)
        try:
            names = [f"s{i}" for i in range(4)]
            for i, n in enumerate(names):
                assert eng.admit(_spec(n, 4 - i)).admitted
            # partitioned routing actually used both servers
            servers = {eng.pool.server_of(n) for n in names}
            assert servers == {0, 1}

            results = {}

            def worker(n):
                results[n] = eng.generate(n, prompt, steps=STEPS)

            threads = [threading.Thread(target=worker, args=(n,))
                       for n in names]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

            for n in names:
                assert results[n].tokens == want, n
                assert len(results[n].decode_latencies_s) == STEPS
            # every decode step went through a BatchingServer dispatch
            total_batched = sum(s.stats.batches for s in eng.pool.servers)
            assert total_batched >= 1
            completed = sum(s.stats.completed for s in eng.pool.servers)
            # 4 streams x (prefill + insert + STEPS decodes)
            assert completed == 4 * (2 + STEPS)
        finally:
            eng.close()

    def test_slots_recycled_across_jobs(self, setup):
        """More sequential jobs than slots: slots must free and be reused."""
        cfg, params = setup
        prompt = np.array([[5, 6]], np.int32)
        eng = ServeEngine(cfg, params, max_seq=32, num_servers=1,
                          batching=True, max_batch=2)
        try:
            for i in range(3):
                assert eng.admit(_spec(f"j{i}", 3 - i, steps=2)).admitted
            for i in range(3):  # sequential: each job acquires + releases
                r = eng.generate(f"j{i}", prompt, steps=2)
                assert len(r.tokens) == 2
            assert len(eng._slots[0].free) == 2  # all slots back
        finally:
            eng.close()

    def test_batched_requires_single_row_prompt(self, setup):
        cfg, params = setup
        eng = ServeEngine(cfg, params, max_seq=32, num_servers=1,
                          batching=True, max_batch=2)
        try:
            assert eng.admit(_spec("w", 1)).admitted
            with pytest.raises(ValueError, match="one sequence"):
                eng.generate("w", np.zeros((2, 4), np.int32), steps=1)
        finally:
            eng.close()

    @pytest.mark.parametrize("paged", [False, True])
    def test_mixed_prompt_lengths_match_unbatched(self, setup, paged):
        """Streams with different prompt lengths (different prefill buckets,
        different live cache lengths) must each reproduce their own
        unbatched tokens."""
        cfg, params = setup
        prompts = {f"m{i}": np.arange(1, n + 1, dtype=np.int32)[None, :] % 100
                   for i, n in enumerate([2, 5, 9])}
        want = {n: _reference_tokens(cfg, params, p)
                for n, p in prompts.items()}

        eng = ServeEngine(cfg, params, max_seq=32, num_servers=1,
                          batching=True, max_batch=4, paged=paged)
        try:
            for i, n in enumerate(prompts):
                assert eng.admit(_spec(n, 3 - i)).admitted
            results = {}

            def worker(n):
                results[n] = eng.generate(n, prompts[n], steps=STEPS)

            threads = [threading.Thread(target=worker, args=(n,))
                       for n in prompts]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for n in prompts:
                assert results[n].tokens == want[n], n
        finally:
            eng.close()

    def test_concurrent_streams_coalesce(self, setup):
        """With one server and concurrently decoding streams, at least one
        device call must carry more than one request."""
        cfg, params = setup
        prompt = np.array([[1, 2, 3]], np.int32)
        eng = ServeEngine(cfg, params, max_seq=64, ordering="fifo",
                          num_servers=1, batching=True, max_batch=4)
        try:
            for i in range(4):
                assert eng.admit(_spec(f"c{i}", 4 - i, steps=16)).admitted
            results = {}

            def worker(n):
                results[n] = eng.generate(n, prompt, steps=16)

            threads = [threading.Thread(target=worker, args=(f"c{i}",))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert all(len(r.tokens) == 16 for r in results.values())
            sizes = eng.pool.servers[0].stats.batch_sizes
            assert max(sizes) > 1, sizes
        finally:
            eng.close()


class TestPagedPoolServing:
    """Paged block-pool decode: bit-identical greedy tokens, slot
    compaction, width bucketing, and block accounting."""

    def test_four_streams_two_servers_match_unbatched(self, setup):
        cfg, params = setup
        prompt = np.array([[1, 2, 3, 4]], np.int32)
        want = _reference_tokens(cfg, params, prompt)

        eng = ServeEngine(cfg, params, max_seq=32, num_servers=2,
                          batching=True, max_batch=4, paged=True,
                          kv_block_size=8)
        try:
            names = [f"p{i}" for i in range(4)]
            for i, n in enumerate(names):
                assert eng.admit(_spec(n, 4 - i)).admitted
            assert {eng.pool.server_of(n) for n in names} == {0, 1}

            results = {}

            def worker(n):
                results[n] = eng.generate(n, prompt, steps=STEPS)

            threads = [threading.Thread(target=worker, args=(n,))
                       for n in names]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

            for n in names:
                assert results[n].tokens == want, n
            # every decode call reported its compaction/width decision
            meta = [m for s in eng.pool.servers for m in s.stats.batch_meta]
            decodes = [m for m in meta if m["kind"] == "decode"]
            assert decodes
            # prompt 4 + 6 steps <= 16 tokens -> 2 blocks of 8; width
            # bucketing must never widen past the pow2 cover of that
            assert all(m["width"] <= 2 for m in decodes)
            prefills = [m for m in meta if m["kind"] == "prefill"]
            assert prefills and all(m["bucket"] == 4 for m in prefills)
            # all blocks released at job end (scratch block still held)
            for st in eng._paged:
                assert st.mgr.blocks_in_use == 1
        finally:
            eng.close()

    def test_single_stream_compacts(self, setup):
        """One live stream in an 8-slot server: the device call must shrink
        to a single row (slot compaction at low occupancy)."""
        cfg, params = setup
        prompt = np.array([[7, 8, 9]], np.int32)
        eng = ServeEngine(cfg, params, max_seq=64, num_servers=1,
                          batching=True, max_batch=8, paged=True,
                          kv_block_size=8)
        try:
            assert eng.admit(_spec("solo", 1)).admitted
            res = eng.generate("solo", prompt, steps=4)
            assert len(res.tokens) == 4
            decodes = [m for m in eng.pool.servers[0].stats.batch_meta
                       if m["kind"] == "decode"]
            assert decodes
            assert all(m["padded"] == 1 and m["compacted"] for m in decodes)
        finally:
            eng.close()

    def test_precompile_visits_all_shape_buckets(self, setup):
        """precompile() must warm every (rows, width) pow2 cell so no
        decode step ever hits a cold trace mid-traffic — each distinct
        cell traced ONCE (the jitted step is shared across servers)."""
        cfg, params = setup
        eng = ServeEngine(cfg, params, max_seq=32, num_servers=2,
                          batching=True, max_batch=4, paged=True,
                          kv_block_size=8)
        try:
            # rows in {1,2,4} x widths in {1,2,4} (nb_max=32/8) = 9 decode
            # cells, plus one migrate (gather+scatter) cell per width = 12
            rep = eng.precompile()
            assert rep.compiled == 12 and rep.skipped == 0
            assert rep.migrate_cells == (1, 2, 4)
            # second call: everything already warm -> all deduped away
            rep2 = eng.precompile()
            assert rep2.compiled == 0 and rep2.skipped == 12
            before = eng._decode_paged._cache_size()
            assert eng.admit(_spec("w", 1)).admitted
            res = eng.generate("w", np.array([[1, 2, 3]], np.int32), steps=4)
            assert len(res.tokens) == 4
            assert eng._decode_paged._cache_size() == before  # no cold trace
        finally:
            eng.close()

    def test_precompile_covers_nonpow2_max_batch(self, setup):
        """max_batch=6 makes the runtime clamp produce a SIX-row cell
        (pow2ceil clamped to the cap); the old pow2-only precompile loop
        missed it, leaving (6, w) traces cold.  The ladder must include the
        cap and the report must count the extra row bucket."""
        cfg, params = setup
        eng = ServeEngine(cfg, params, max_seq=32, num_servers=1,
                          batching=True, max_batch=6, paged=True,
                          kv_block_size=8)
        try:
            assert eng._row_buckets == (1, 2, 4, 6)
            rep = eng.precompile()
            # rows {1,2,4,6} x widths {1,2,4} = 12 decode cells, + the 3
            # per-width migrate cells
            assert rep.compiled == 15
            assert (6, 1) in rep.decode_cells
        finally:
            eng.close()

    def test_traffic_aware_precompile_bumps_cold_cells(self, setup):
        """precompile(traffic=...) compiles only the predicted-hit cells
        plus the largest-cell safe fallback; a cold cell at runtime bumps
        UP to a warm cover instead of stalling on XLA compilation."""
        cfg, params = setup
        eng = ServeEngine(cfg, params, max_seq=32, num_servers=1,
                          batching=True, max_batch=4, paged=True,
                          kv_block_size=8)
        try:
            hot = {("decode", 2, 2)}
            rep = eng.precompile(traffic=hot)
            # the hot cell + the (4, 4) fallback + the width-4 migrate
            # fallback (a steal can hit any stream regardless of traffic)
            assert rep.compiled == 3
            assert set(rep.decode_cells) == {(2, 2), (4, 4)}
            assert rep.migrate_cells == (4,)
            assert rep.skipped == (9 - 2) + (3 - 1)
            before = eng._decode_paged._cache_size()
            assert eng.admit(_spec("t", 1)).admitted
            res = eng.generate("t", np.array([[1, 2, 3]], np.int32), steps=4)
            assert len(res.tokens) == 4
            # the 1-row/width-1 steps ran in the warm (2, 2) cell: no new
            # trace was compiled mid-traffic
            assert eng._decode_paged._cache_size() == before
            decodes = [m for m in eng.pool.servers[0].stats.batch_meta
                       if m["kind"] == "decode"]
            assert decodes and all(
                (m["padded"], m["width"]) == (2, 2) and not m["cold"]
                for m in decodes)
        finally:
            eng.close()

    @pytest.mark.parametrize("program", ["_decode_paged", "_last_token"])
    def test_traffic_precompile_leaves_serving_no_trace(self, setup,
                                                        program):
        """After precompile(traffic=...), as the launcher and the benchmark
        warm an engine, serving a job's prefill and decode steps adds no
        trace to the paged decode step (its on-device pick included) nor
        to the prefill's last-position pick."""
        cfg, params = setup
        eng = ServeEngine(cfg, params, max_seq=32, num_servers=1,
                          batching=True, max_batch=4, paged=True,
                          kv_block_size=8)
        try:
            prompt = np.array([[1, 2, 3]], np.int32)
            eng.tune_buckets([3], steps_hint=4)
            cells = eng.traffic_cells([(3, 4)], concurrency=1)
            eng.precompile((3,), traffic=cells)
            jitted = getattr(eng, program)
            before = jitted._cache_size()
            assert before > 0
            assert eng.admit(_spec("t", 1)).admitted
            res = eng.generate("t", prompt, steps=4)
            assert len(res.tokens) == 4
            assert jitted._cache_size() == before
        finally:
            eng.close()

    def test_tune_buckets_minimizes_padding_waste(self, setup):
        """Bucket auto-tuning: with max_buckets=2 and short prompts the
        prefill ladder collapses to {tight cover, max_seq} and decode
        widths to {tight cover, nb_max} — and the tuned engine still
        generates correctly (the cover bucket always survives)."""
        cfg, params = setup
        eng = ServeEngine(cfg, params, max_seq=32, num_servers=1,
                          batching=True, max_batch=4, paged=True,
                          kv_block_size=8)
        try:
            pb, wb = eng.tune_buckets([3, 3, 4], steps_hint=3,
                                      max_buckets=2)
            assert pb == (4, 32)   # tight cover 4 + forced max_seq
            assert wb == (1, 4)    # every need is 1 block + forced nb_max
            rep = eng.precompile()
            # rows {1,2,4} x tuned widths {1,4} = 6 decode cells, + the 2
            # tuned-width migrate cells
            assert rep.compiled == 8
            assert eng.admit(_spec("b", 1)).admitted
            res = eng.generate("b", np.array([[1, 2, 3]], np.int32),
                               steps=4)
            assert len(res.tokens) == 4
        finally:
            eng.close()

    def test_pool_exhaustion_rejects_before_dispatch(self, setup):
        cfg, params = setup
        from repro.serving.kvcache import OutOfBlocksError

        eng = ServeEngine(cfg, params, max_seq=32, num_servers=1,
                          batching=True, max_batch=2, paged=True,
                          kv_block_size=8, kv_blocks=3)  # scratch + 2 blocks
        try:
            assert eng.admit(_spec("big", 1)).admitted
            with pytest.raises(OutOfBlocksError):
                # needs ceil((17+6)/8) = 3 blocks, only 2 available
                eng.generate("big", np.zeros((1, 17), np.int32), steps=6)
            assert eng._paged[0].mgr.blocks_in_use == 1  # nothing leaked
        finally:
            eng.close()

    def test_paged_requires_declared_family(self):
        """A stack whose cache_family declaration is stripped has NO paged
        path — the engine must refuse, never silently fall back to dense."""
        import dataclasses

        from repro.configs.registry import get_config as gc

        cfg = dataclasses.replace(gc("deepseek_v2_lite_16b").reduced(),
                                  cache_family="")
        params = M.init_params(cfg, jax.random.PRNGKey(2))
        with pytest.raises(ValueError, match="paged decode unsupported"):
            ServeEngine(cfg, params, max_seq=32, batching=True, paged=True)
