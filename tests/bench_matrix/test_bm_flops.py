"""`flops.py` against a hand count at Mistral-7B-v0.3's widths."""

import pytest

from bench_matrix import flops, spec

D4 = spec.load("configs", "mistral-7b-v0.3-d4")
D16 = spec.load("configs", "mistral-7b-v0.3-d16")


def test_parameter_counts_by_hand():
    m = flops.matmul_params(D4)
    # q 4096x4096, k and v 4096x1024, o 4096x4096; three 4096x14336 matrices
    assert m["attn"] == 16777216 + 2 * 4194304 + 16777216 == 41943040
    assert m["mlp"] == 3 * 58720256 == 176160768
    assert m["head"] == 4096 * 32768 == 134217728
    assert flops.param_count(D4) == 4 * (218103808 + 8192) + 4096 + 2 * 134217728
    assert round(flops.param_count(D4) / 1e6) == 1141
    assert round(flops.param_count(D16) / 1e6) == 3758


def test_training_flops_per_token_by_hand():
    # 6 flops a matmul parameter; attention 2 matmuls x 2 flops x 4096 keys
    # x 4096 width, halved by the causal mask, tripled for the backward pass
    attn = 3 * 4 * 4096 * 4096 * 0.5
    want4 = 6 * (4 * 218103808 + 134217728) + 4 * attn
    assert flops.train_flops_per_token(D4, 4096) == pytest.approx(want4)
    assert want4 == pytest.approx(6.44e9, rel=1e-2)
    assert flops.train_flops_per_token(D16, 4096) == pytest.approx(2.335e10, rel=1e-2)


def test_flash_call_and_roofline():
    c = flops.flash_call(2, 4096, 32, 128)
    mm = 2 * 2 * 32 * 4096 * 4096 * 128 * 0.5
    assert c["forward_flops"] == 2 * mm and c["backward_flops"] == 5 * mm
    assert c["forward_bytes"] == 4 * 2 * 4096 * 32 * 128 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = flops.roofline_seconds(c["forward_flops"], c["forward_bytes"], peaks)
    assert r["bound"] == "compute"
    assert r["seconds"] == pytest.approx(c["forward_flops"] / 197e12)
    assert flops.roofline_seconds(1.0, 1e9, peaks)["bound"] == "memory"


def test_paged_decode_bytes_by_hand_for_three_rows():
    # rows attending 17, 512 and 4097 keys over 8 KV heads x 128 in bf16:
    # K and V of every key, 8 * 128 * 2 bytes each = 4096 bytes a key
    c = flops.paged_decode_call([17, 512, 4097], 32, 8, 128)
    assert c["bytes"] == (17 + 512 + 4097) * 4096 == 18948096
    assert c["flops"] == 4 * (17 + 512 + 4097) * 32 * 128  # QK^T and PV, 32 heads
    assert flops.paged_decode_call([17, 512, 4097], 32, 8, 128, itemsize=1)["bytes"] == (
        18948096 // 2)
    # a step in which no row decodes needs nothing
    assert flops.paged_decode_call([], 32, 8, 128) == {"bytes": 0.0, "flops": 0.0}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = flops.roofline_seconds(c["flops"], c["bytes"], peaks)
    assert r["bound"] == "memory" and r["seconds"] == pytest.approx(18948096 / 819e9)


def test_distinct_keys_of_a_table_where_two_rows_hold_the_same_leading_blocks():
    import numpy as np

    # blocks of 16 keys; rows 0 and 1 open with the same two blocks (7, 3: a
    # shared prefix of 32 keys), row 2 shares nothing; 99 marks no block
    tables = np.array([[7, 3, 11, 99], [7, 3, 12, 13], [20, 21, 99, 99]])
    keys = np.array([40, 50, 17])
    # 32 shared keys once, 8 of block 11, 16 + 2 of blocks 12 and 13, 16 + 1
    assert flops.distinct_keys(tables, keys, 16) == 32 + 8 + 18 + 17 == 75
    assert keys.sum() - 75 == 32  # what the second holder does not read again
    # a shared block that one row reads only in part counts at the most any reads
    assert flops.distinct_keys(tables[:2], np.array([20, 50]), 16) == 50
    assert flops.distinct_keys(tables[:2], np.array([20, 10]), 16) == 20
    # nothing shared: every row's keys are its own
    assert flops.distinct_keys(tables[[0, 2]], keys[[0, 2]], 16) == 57
    assert flops.distinct_keys(tables[:0], keys[:0], 16) == 0
    # bytes go by the distinct keys, FLOPs by every (row, key) pair
    c = flops.paged_decode_call(keys.tolist(), 32, 8, 128, distinct=75)
    assert c["bytes"] == 75 * 4096 and c["flops"] == 4 * 107 * 32 * 128
    assert flops.paged_decode_call(keys.tolist(), 32, 8, 128)["bytes"] == 107 * 4096


DEV = "/device:TPU:0"
ARGS = {"program": "^jit_step$", "scope": "(^|/)cache_attention(/|$)"}
PATH = "jit(step)/TransformerLM/layers_{}/attn/cache_attention/{}"


def _decode_env(monkeypatch, steps, runs, layers=2, each_ps=1_000_000, cut_last=False):
    """A trace with `runs` runs of `jit_step`, each holding one kernel call
    a layer of `each_ps` under `cache_attention`, the work lists' operation
    beside the first and a matmul outside the scope; and the runner's record
    of the steps it dispatched."""
    from bench_matrix.readers import ReadEnv, scope_time
    from bench_matrix.reduce import scopes, xplane

    ops, on_line, gap = [], [], 100_000_000
    for i in range(runs):
        start = i * gap
        if not (cut_last and i == runs - 1):
            on_line.append(("jit_step", 7, start, gap - 1_000_000))
        ops.append((PATH.format(0, "work_lists/add"), 7, start + 500, 0))
        for k in range(layers if not (cut_last and i == runs - 1) else 1):
            ops.append((PATH.format(k, "pallas_call"), 7, start + 1000 + k * 5_000_000, each_ps))
        ops.append(("jit(step)/TransformerLM/layers_0/mlp/down_proj/dot_general", 7,
                    start + 50_000_000, 7_000_000))
    sc = scopes.Scopes(ops={DEV: ops}, runs={DEV: on_line})
    monkeypatch.setattr(scope_time, "_scopes", lambda env: sc)
    said = []
    cfg = dict(D16, num_hidden_layers=layers)
    return ReadEnv(cell={"config": cfg, "name": "tiny"}, samples={"decode_steps": steps},
                   trace=xplane.Trace(devices={DEV: []}),
                   peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                   chips=1, memory_peak_bytes=0, say=said.append), said


def _steps(*rows, shared=0):
    from _tiny import kept_steps

    return kept_steps(rows, shared)["decode_steps"]


def test_paged_decode_roofline_counts_decoding_rows_only(monkeypatch):
    read = spec.module("readers", "paged_decode_roofline").read
    # two steps of an 8-slot engine: three rows decode in the first, one in
    # the second; the five and seven parked or mid-prefill rows are not in
    # the lists and count nothing. Two layers: four kernel calls of 1 us.
    steps = _steps([17, 512, 4097], [513])
    need = 2 * (17 + 512 + 4097 + 513) * 4096  # bytes, both layers
    env, said = _decode_env(monkeypatch, steps, runs=2)
    got = read(ARGS, env)
    assert got == pytest.approx(100.0 * (need / 819e9) / 4e-6)
    assert "2 dispatches kept, 2 runs" in said[-1] and "2 paired, 2 counted" in said[-1]
    # an implementation twice as slow reads half
    assert read(ARGS, _decode_env(monkeypatch, steps, 2, each_ps=2_000_000)[0]) == (
        pytest.approx(got / 2))
    # rows that hold the same 256 leading keys: the bytes of the distinct keys
    shared = _steps([300, 400, 500], shared=512) + _steps([513])
    env, _ = _decode_env(monkeypatch, shared, runs=2)
    assert read(ARGS, env) == pytest.approx(
        100.0 * (2 * (300 + 400 + 500 + 513 - 512) * 4096 / 819e9) / 4e-6)


@pytest.mark.parametrize("case", ["one_run_more", "one_run_fewer", "last_run_cut",
                                  "a_step_not_counted"])
def test_a_reader_handed_runs_and_steps_that_differ_by_one_still_gives_a_number(
        monkeypatch, case):
    """The flushes make runs and dispatches pair one to one; where they do
    not, the unpaired end is dropped, both counts are said, and the share
    is of the pairs that are left: never `no number`, never 0."""
    read = spec.module("readers", "paged_decode_roofline").read
    steps = _steps([100, 200], [101, 201], [102, 202])
    layers_bytes = 2 * 4096
    if case == "one_run_more":  # the leading run was dispatched before the slice
        env, said = _decode_env(monkeypatch, steps[1:], runs=3)
        pairs, text = steps[1:], "2 dispatches kept, 3 runs of the program in the slice"
    elif case == "one_run_fewer":  # the last dispatch's run is not in the trace
        env, said = _decode_env(monkeypatch, steps, runs=2)
        pairs, text = steps[:2], "3 dispatches kept, 2 runs of the program in the slice"
    elif case == "last_run_cut":  # ... or was cut: no event on the modules line
        env, said = _decode_env(monkeypatch, steps, runs=3, cut_last=True)
        pairs, text = steps[:2], "3 dispatches kept, 2 runs of the program in the slice"
    else:  # the runner could not tell one step's rows: it goes with its run
        steps[1]["distinct"] = None
        env, said = _decode_env(monkeypatch, steps, runs=3)
        pairs, text = [steps[0], steps[2]], "3 paired, 2 counted"
    got = read(ARGS, env)
    need = layers_bytes * sum(sum(s["keys"]) for s in pairs)
    assert got == pytest.approx(100.0 * (need / 819e9) / (len(pairs) * 2 * 1e-6))
    assert text in said[-1] and f"{len(pairs)} counted" in said[-1]


@pytest.mark.parametrize("case", ["no_trace", "no_scope_in_the_program", "no_decode_steps",
                                  "not_traced", "no_run_of_the_program"])
def test_paged_decode_roofline_is_none_when_there_is_nothing_to_read(monkeypatch, case):
    from bench_matrix.readers import scope_time
    from bench_matrix.reduce import scopes

    read = spec.module("readers", "paged_decode_roofline").read
    env, said = _decode_env(monkeypatch, _steps([17, 512, 4097], [513]), runs=2)
    if case == "no_trace":
        monkeypatch.setattr(scope_time, "_scopes", lambda env: None)
    elif case == "no_scope_in_the_program":  # an engine whose step runs another path
        bare = scopes.Scopes(ops={DEV: [("jit(step)/TransformerLM/lm_head/dot_general", 7, 5, 9)]},
                             runs={DEV: [("jit_step", 7, 0, 2000)]})
        monkeypatch.setattr(scope_time, "_scopes", lambda env: bare)
    elif case == "no_decode_steps":
        env.samples["decode_steps"] = []
    elif case == "not_traced":  # --trace 0: the runner keeps no step
        del env.samples["decode_steps"]
    else:
        other = scopes.Scopes(ops={DEV: [(PATH.format(0, "pallas_call"), 9, 5, 9)]},
                              runs={DEV: [("jit_prefill_chunk", 9, 0, 2000)]})
        monkeypatch.setattr(scope_time, "_scopes", lambda env: other)
    assert read(ARGS, env) is None


def _flash_env(forward_calls_a_layer, fwd_ns=2_000_000, bwd_ns=5_000_000, remat=True):
    """Two steps of a two-layer model traced on one device: per layer and
    step `forward_calls_a_layer` calls of `flash_fwd` and one of `flash_bwd`."""
    from bench_matrix.readers import ReadEnv
    from bench_matrix.reduce import xplane

    events, t = [], 0
    for i in range(2 * 2):
        for kernel, ns in [("flash_fwd", fwd_ns)] * forward_calls_a_layer + [("flash_bwd", bwd_ns)]:
            events.append((f"{kernel}.{i} custom-call bf16[64,4096,128] tpu_custom_call", t, t + ns))
            t += ns + 1000
    events.append(("fusion.9 fusion bf16[2,4096,4096]", t, t + 9_000_000))
    said = []
    return ReadEnv(
        cell={"config": dict(D4, num_hidden_layers=2)},
        samples={"trace_steps": 2, "global_batch": 2, "seq": 4096, "remat": remat},
        trace=xplane.Trace(devices={"/device:TPU:0": events}),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        chips=1, memory_peak_bytes=0, say=said.append), said


def test_flash_roofline_counts_one_forward_pass_a_layer_whatever_the_step_recomputes():
    """A step NEEDS, a layer, one forward pass (2 causal products) and one
    backward (5): 7 x 137.4 GFLOP at `lm_train_1chip`'s shape. The reader
    counted 2 x 2 + 5 = 9 under `remat: true` (ledger, PRs 34-38: 84.93 and
    87.06) where the fitted step runs one forward call, and would have passed
    100 % at kernels 81.7 % efficient. What the trace holds is said, not
    counted: a rung that runs the forward twice reads as lost share."""
    read = spec.module("readers", "flash_roofline").read
    args = spec.load("layer_metrics", "flash_roofline")["args"]
    mm = 2 * 2 * 32 * 4096 * 4096 * 128 * 0.5
    assert mm == pytest.approx(137.4e9, rel=1e-3)
    env, said = _flash_env(1)
    need = 2 * 2 * 7 * mm  # steps x layers x products
    got = read(args, env)
    assert got == pytest.approx(100.0 * (need / 197e12) / (4 * 7e-3))
    assert "'flash_fwd': 4" in said[-1] and "'flash_bwd': 4" in said[-1]
    # the sample's `remat` flag changes nothing: the count is what the step needs
    assert read(args, _flash_env(1, remat=False)[0]) == pytest.approx(got)
    # kernels at the MXU's peak read 100, never more
    fast = _flash_env(1, fwd_ns=round(2 * mm / 197e12 * 1e9), bwd_ns=round(5 * mm / 197e12 * 1e9))[0]
    assert read(args, fast) == pytest.approx(100.0, rel=1e-5)
    # a step that runs the forward call twice a layer: the same need, more time
    env, said = _flash_env(2)
    assert read(args, env) == pytest.approx(100.0 * (need / 197e12) / (4 * 9e-3))
    assert "'flash_fwd': 8" in said[-1]
    # nothing to read: no trace, no kernel event, a serve cell's samples
    env.trace.devices["/device:TPU:0"] = env.trace.devices["/device:TPU:0"][-1:]
    assert read(args, env) is None
    env.trace = None
    assert read(args, env) is None
    env, _ = _flash_env(1)
    env.samples = {}
    assert read(args, env) is None


def test_serve_mfu_on_a_window_counted_by_hand():
    """A window of two prefill chunks, an attached prefix and three decode
    steps, at Mistral-7B-v0.3-d16's widths. Request A prefills 600 tokens as
    chunks (0, 512) and (512, 88); request B's first 2048 tokens were ATTACHED
    from the prefix cache and are in no chunk, its own 64 are chunk (2048, 64);
    the steps decode rows of [601, 2113], [602, 2114] and [603] keys. A token
    costs 2 FLOPs a matmul parameter (16 layers and the head) and, a layer,
    QK^T and PV over the keys it attends: 4 x 4096 x keys."""
    from bench_matrix import modelglue
    from bench_matrix.readers import ReadEnv

    read = spec.module("readers", "serve_mfu").read
    chunks = [[0, 512], [512, 88], [2048, 64]]
    decode = [601, 2113, 602, 2114, 603]
    matmuls = 2 * (16 * 218103808 + 134217728)
    assert matmuls == 7_247_757_312
    # `flops.py` counts a token at position p as attending p + 1/2 keys (the
    # causal mean over a sequence, seq / 2, taken apart): tokens [a, b) attend
    # (b^2 - a^2) / 2 keys in all, a decoding row of k keys k - 1/2
    attended = sum(((a + n) ** 2 - a ** 2) / 2 for a, n in chunks) + sum(k - 0.5 for k in decode)
    want = (512 + 88 + 64 + 5) * matmuls + 16 * 4 * 4096 * attended
    f = modelglue.forward_flops(D16)
    assert f(0, 512) + f(512, 88) == pytest.approx(f(0, 600))  # chunking changes nothing
    assert f(600, 1) == pytest.approx(matmuls + 16 * 4 * 4096 * 600.5)
    said = []
    env = ReadEnv(
        cell={"config": D16}, trace=None, chips=1, memory_peak_bytes=0, say=said.append,
        samples={"computed": {"chunks": chunks, "decode_keys": decode}, "window": [10.0, 10.5]},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    got = read({}, env)
    assert got == pytest.approx(100.0 * want / 0.5 / 197e12)
    assert 0 < got < 100 and "664 tokens in 3 prefill chunks" in said[-1]
    # the attached 2048 tokens are not computed and do not count
    env.samples["computed"]["chunks"] = [[0, 512], [512, 88], [0, 2112]]
    assert read({}, env) > 3 * got
    # a runner that kept no such record: left out
    env.samples = {"window": [10.0, 10.5]}
    assert read({}, env) is None
    # every serve cell lists it, and it moves what they report
    m = spec.load("layer_metrics", "serve_mfu_pct")
    assert (m["layer"], m["source"], m["moves"], m["better"]) == (
        "model", "host_clock", "serve_tokens_per_s", "higher")
    for name in spec.names("workloads"):
        cell = spec.load("workloads", name)
        assert ("serve_mfu_pct" in cell["per_layer"]) == (
            "serve_tokens_per_s" in cell["end_to_end"]), name


@pytest.mark.parametrize("name", ["laguna-xs.2-d5", "olmo-hybrid-7b-d16",
                                  "openpangu-ultra-moe-718b-d7", "xing4.0-29b-a4b-d8"])
def test_forward_flops_takes_every_glue_s_mean_apart_into_its_tokens(name):
    """`modelglue.forward_flops` needs no count of its own a glue: a prefix's
    FLOPs are its length times the glue's mean, so the tokens between two
    prefixes cost the difference, and a later token (more keys) costs more
    than an earlier one, up to a layer's window."""
    from bench_matrix import modelglue

    cfg = spec.load("configs", name)
    f = modelglue.forward_flops(cfg)
    whole = 4096 * modelglue.train_flops_per_token(cfg, 4096) / 3
    assert f(0, 4096) == pytest.approx(whole)
    assert f(0, 1000) + f(1000, 3096) == pytest.approx(whole)
    assert sum(f(p, 1) for p in range(64)) == pytest.approx(f(0, 64))
    assert f(4095, 1) > f(100, 1) > 0 and f(0, 0) == 0
