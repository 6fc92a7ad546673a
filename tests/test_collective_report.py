"""Collective-traffic accounting (debugger.collective_report) — the
scaling-efficiency evidence producible without pod hardware (VERDICT r2
#8; reference anchor: benchmark/README.md:70-95 scaling tables)."""

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import debugger, optimizer as opt
from paddle_tpu.debugger import _parse_hlo_collectives
from paddle_tpu.models import transformer
from paddle_tpu.parallel import transformer_tp_rules


def test_parse_hlo_collectives():
    hlo = """
  %all-reduce.7 = f32[128,64]{1,0} all-reduce(f32[128,64]{1,0} %add.3), replica_groups={{0,1,2,3}}, to_apply=%sum
  %ag = (f32[256]{0}, f32[256]{0}) all-gather-start(f32[64]{0} %p), replica_groups={{0,1},{2,3}}, dimensions={0}
  %agd = f32[256]{0} all-gather-done((f32[256]{0}, f32[256]{0}) %ag)
  %cp = bf16[32,16]{1,0} collective-permute(bf16[32,16]{1,0} %x), source_target_pairs={{0,1},{1,2}}
  %fusion.1 = f32[10]{0} fusion(f32[10]{0} %y), kind=kLoop
"""
    got = _parse_hlo_collectives(hlo)
    kinds = [k for k, _, _ in got]
    assert kinds == ["all-reduce", "all-gather", "collective-permute"]
    ar = got[0]
    assert ar[1] == 128 * 64 * 4 and ar[2] == 4
    ag = got[1]  # async start: tuple aliases (operand, result) — count
    assert ag[1] == 256 * 4 and ag[2] == 2  # the result only, once
    cp = got[2]
    assert cp[1] == 32 * 16 * 2


def test_parse_hlo_async_start_counts_result_once():
    """all-gather-start output tuples include the operand and u32
    contexts; only the (largest) result element is the payload. Variadic
    all-reduce tuples are all results and sum. Iota replica_groups and
    /*index=N*/ comments parse."""
    hlo = """
  %ags = (f32[64]{0}, f32[256]{0}, u32[], u32[]) all-gather-start(f32[64]{0} %p), replica_groups=[2,4]<=[8], dimensions={0}
  %cps = (bf16[32]{0}, bf16[32]{0}) collective-permute-start(bf16[32]{0} %x), source_target_pairs={{0,1}}
  %arv = (f32[10]{0}, /*index=1*/f32[20]{0}) all-reduce-start(f32[10]{0} %a, f32[20]{0} %b), replica_groups={}
"""
    got = _parse_hlo_collectives(hlo, fallback_group_size=8)
    assert got[0] == ("all-gather", 256 * 4, 4)       # result, iota group size
    assert got[1] == ("collective-permute", 32 * 2, 8)  # counted once
    assert got[2] == ("all-reduce", (10 + 20) * 4, 8)   # variadic: summed


def test_parse_hlo_collectives_with_tpu_layouts():
    """The TPU's compiled text carries tiled layouts with parentheses
    inside a tuple shape; the async exchange of the tp block and a
    variadic gradient all-reduce are both such lines."""
    hlo = """
  %collective-permute-start = (bf16[8,1024,1280]{2,1,0:T(8,128)(2,1)S(1)}, bf16[8,1024,1280]{2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%add_convert_fusion.4), channel_id=1, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
  %collective-permute-done = bf16[8,1024,1280]{2,1,0:T(8,128)(2,1)} collective-permute-done(%collective-permute-start)
  %all-reduce.4 = (f32[2,2560]{1,0:T(2,128)}, /*index=1*/f32[2,1280,2560]{2,1,0:T(8,128)}) all-reduce(%a, %b), channel_id=5, replica_groups={{0,2},{1,3}}, to_apply=%sum
  %all-gather.4 = bf16[16,1024,1280]{2,1,0:T(8,128)(2,1)S(1)} all-gather(%x), channel_id=1, replica_groups={{0,1},{2,3}}, dimensions={0}
"""
    assert _parse_hlo_collectives(hlo, fallback_group_size=4) == [
        ("collective-permute", 8 * 1024 * 1280 * 2, 4),
        ("all-reduce", (2 * 2560 + 2 * 1280 * 2560) * 4, 2),
        ("all-gather", 16 * 1024 * 1280 * 2, 2)]


def test_reduce_scatter_wire_is_result_times_n_minus_1():
    """A reduce-scatter RESULT is 1/n of the logical input; ring wire is
    result*(n-1), not result*(n-1)/n — the dominant FSDP collective must
    not be undercounted by n (review finding)."""
    from paddle_tpu.debugger import _parse_hlo_collectives as parse

    from paddle_tpu.debugger import _wire_factor

    hlo = "%rs = f32[8]{0} reduce-scatter(f32[32]{0} %g), replica_groups={{0,1,2,3}}, dimensions={0}, to_apply=%sum"
    ((kind, payload, gsize),) = parse(hlo)
    assert (kind, payload, gsize) == ("reduce-scatter", 32, 4)
    assert payload * _wire_factor(kind, gsize) == 96.0  # 32B result -> 96B wire
    assert _wire_factor("all-gather", 4) == pytest.approx(0.75)
    assert _wire_factor("all-reduce", 4) == pytest.approx(1.5)


def _trainer(mesh, rules, strategy=None):
    cfg = transformer.base_config(src_vocab=64, trg_vocab=64, d_model=32,
                                  d_inner=64, num_heads=4, num_encoder_layers=2,
                                  num_decoder_layers=2, dropout=0.0)
    prog = pt.build(transformer.make_model(cfg))
    rng = np.random.RandomState(0)
    feed = {"src_ids": rng.randint(3, 64, (8, 16)).astype(np.int32),
            "trg_ids": rng.randint(3, 64, (8, 16)).astype(np.int32),
            "labels": rng.randint(3, 64, (8, 16)).astype(np.int32)}
    tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                    sharding_rules=rules, strategy=strategy)
    tr.startup(sample_feed=feed)
    return tr, feed


def test_collective_report_dp_sees_grad_allreduce():
    """Pure DP: the dominant collective must be the gradient all-reduce,
    with payload on the order of the param bytes."""
    mesh = pt.make_mesh({"dp": 8})
    tr, feed = _trainer(mesh, pt.parallel.replicated())
    rep = debugger.collective_report(tr, feed)
    assert "all-reduce" in rep["collectives"], rep
    param_mb = sum(v.size * 4 for v in jax.tree.leaves(tr.scope.params)) / 1e6
    ar_mb = rep["collectives"]["all-reduce"]["payload_mb"]
    # grads for every param get all-reduced at least once (loss/metrics
    # add small extras; XLA may fuse or split, so bound loosely)
    assert ar_mb > 0.5 * param_mb, (ar_mb, param_mb)
    assert rep["est_wire_mb_per_device"] > 0
    assert rep["mesh"] == {"dp": 8}


def test_collective_report_interleave_traffic_tradeoff():
    """The interleaved pipeline's documented cost is V× more
    collective-permute traffic: M·V+P-1 ticks of ring hops vs M+P-1.
    collective_report's static walk counts the in-scan ppermute ONCE
    (documented limitation), so the evidence is structural: the permute
    is present in the inventory, and the tick-scan length in the traced
    program grows exactly per _schedule_ticks."""
    import re

    from paddle_tpu.parallel import DistStrategy
    from paddle_tpu.parallel.pipeline import _schedule_ticks

    def _pp_trainer(interleave):
        cfg = transformer.base_config(src_vocab=64, trg_vocab=64, d_model=32,
                                      d_inner=64, num_heads=4,
                                      num_encoder_layers=4,
                                      num_decoder_layers=4, dropout=0.0,
                                      stacked=True)
        prog = pt.build(transformer.make_model(cfg))
        rng = np.random.RandomState(0)
        feed = {"src_ids": rng.randint(3, 64, (8, 16)).astype(np.int32),
                "trg_ids": rng.randint(3, 64, (8, 16)).astype(np.int32),
                "labels": rng.randint(3, 64, (8, 16)).astype(np.int32)}
        mesh = pt.make_mesh({"pp": 2}, devices=jax.devices()[:2])
        tr = pt.Trainer(prog, opt.Adam(1e-3), loss_name="loss", mesh=mesh,
                        sharding_rules=transformer_tp_rules(),
                        strategy=DistStrategy(pp_microbatches=4,
                                              pp_interleave=interleave))
        tr.startup(sample_feed=feed)
        return tr, feed

    for v in (1, 2):
        tr, feed = _pp_trainer(v)
        rep = debugger.collective_report(tr, feed)
        assert "collective-permute" in rep["collectives"], rep
        jaxpr = str(jax.make_jaxpr(
            lambda p, o, s, r, f, ls: tr._loss_and_aux(p, s, r, f))(
                tr.scope.params, tr.scope.opt_state, tr.scope.state,
                jax.random.PRNGKey(0), feed, {}))
        lengths = {int(m.group(1)) for m in re.finditer(r"length=(\d+)", jaxpr)}
        want = _schedule_ticks(4, 2, v)   # m=4, p=2: 5 ticks at v=1, 9 at v=2
        assert want in lengths, (v, want, sorted(lengths))


def test_collective_report_3d_mesh_shows_sharding_collectives():
    """dp×fsdp×tp: fsdp adds param all-gathers, tp adds activation
    collectives — the report must show more collective KINDS than pure
    DP's single fused grad all-reduce (total wire bytes can be lower:
    fsdp's gather/scatter halves beat 2x all-reduce)."""
    mesh_3d = pt.make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    tr_3d, feed_3d = _trainer(mesh_3d, transformer_tp_rules())
    rep_3d = debugger.collective_report(tr_3d, feed_3d)

    kinds_3d = set(rep_3d["collectives"])
    assert "all-gather" in kinds_3d, rep_3d  # fsdp param gathers
    assert len(kinds_3d) > 1, rep_3d  # not just the grad all-reduce
    assert rep_3d["est_wire_mb_per_device"] > 0


def test_accum_grad_exchange_is_per_microbatch():
    """Pin what the comments in executor.py, parallel/strategy.py and
    analysis/rules.py cite: under GSPMD
    the dp grad all-reduce sits INSIDE the accum_steps scan body — the
    partitioner reduces every microbatch's gradients instead of
    hoisting one exchange past the accumulator, so accumulation is a
    memory lever, NOT a wire lever. The day this fails is the day the
    exchange got hoisted (partitioner upgrade or the shard_map
    follow-up): celebrate, then correct those comments and invert this
    assertion."""
    import re

    from paddle_tpu.parallel import DistStrategy

    mesh = pt.make_mesh({"dp": 8})
    tr, feed = _trainer(mesh, pt.parallel.replicated(),
                        strategy=DistStrategy(accum_steps=4))
    rep = debugger.collective_report(tr, feed)
    assert "all-reduce" in rep["collectives"], rep

    # structural check (the static walk counts in-scan collectives once,
    # so collective_report alone cannot see loop placement): parse the
    # while-BODY computations with the same collective parser the
    # report uses (it handles variadic/tuple-typed all-reduce forms)
    # and require GRAD-ORDER payload — a stray scalar loss/metric mean
    # in some loop must neither satisfy nor break the pin
    hlo = debugger._lower_step(tr, feed).compile().as_text()
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    blocks = re.split(r"\n(?=[%\w].*\{)", hlo)
    in_body_ar_bytes = 0.0
    for block in blocks:
        header = block.split("\n", 1)[0]
        name = re.match(r"%?([\w.\-]+)", header.lstrip())
        if name and name.group(1) in bodies:
            in_body_ar_bytes += sum(
                payload for kind, payload, _ in
                _parse_hlo_collectives(block, fallback_group_size=8)
                if kind == "all-reduce")
    param_bytes = sum(v.size * 4 for v in jax.tree.leaves(tr.scope.params))
    assert in_body_ar_bytes > 0.5 * param_bytes, (
        f"only {in_body_ar_bytes:.0f}B of all-reduce inside loop bodies "
        f"vs {param_bytes:.0f}B of params: the grad exchange got hoisted "
        "— accumulation became a wire lever: correct the comments that "
        "cite this test and invert it")


def test_collective_report_counts_the_tp_blocks_exchanges():
    """A stacked GPT on dp2 x tp2 exchanges the block's activation in
    chunks under its matmuls (layers/stacked.py ``_batch_sharded``):
    the report holds them as ``collective-permute``, each of a layer's
    11 once (they sit in the scan's loop bodies: 4 forward, 3 in remat's
    second forward, 4 backward), with the bytes of one chunk. The train
    cell's ``correct`` reads this report: at least one collective."""
    from paddle_tpu.models import gpt

    d, seq, batch = 32, 16, 8
    cfg = gpt.base_config(vocab_size=64, max_len=seq, d_model=d, d_inner=64,
                          num_heads=4, num_layers=2, use_flash=False,
                          remat=True)
    rng = np.random.RandomState(0)
    feed = {"ids": rng.randint(3, 64, (batch, seq)).astype(np.int32),
            "labels": rng.randint(3, 64, (batch, seq)).astype(np.int32)}
    mesh = pt.make_mesh({"dp": 2, "tp": 2}, devices=jax.devices("cpu")[:4])
    tr = pt.Trainer(pt.build(gpt.make_model(cfg)), opt.SGD(0.1),
                    loss_name="loss", mesh=mesh,
                    sharding_rules=transformer_tp_rules())
    tr.startup(sample_feed=feed)
    rep = debugger.collective_report(tr, feed)
    hops = rep["collectives"]["collective-permute"]
    assert hops["count"] == 11, rep["collectives"]
    chunk_mb = batch // 2 // 2 * seq * d * 4 / 1e6     # float32 on the CPU
    assert hops["payload_mb"] == pytest.approx(11 * chunk_mb)
    assert hops["wire_mb"] == pytest.approx(hops["payload_mb"])
    # the gradient exchange over dp is still an all-reduce
    assert rep["collectives"]["all-reduce"]["count"] >= 1
