"""Tests of the yardstick itself (BENCHMARK.json + benchmark/), on the
CPU at tiny sizes: the manifest is legal, every cell's files are found
by name, the generators repeat, the arithmetic is right, and a fifth
cell can be added as new files plus one entry."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, harness, stats  # noqa: E402
from benchmark.traffic import lengths, serve_closed, serve_open, train_stream  # noqa: E402
from benchmark.trace import xplane  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import manifest_checks as checks  # noqa: E402  (this directory)

MANIFEST = harness.load_manifest()
CELLS = checks.names(MANIFEST, "workloads")
E2E = checks.names(MANIFEST, "end_to_end")
LAYER = checks.names(MANIFEST, "per_layer")


# -- the manifest -----------------------------------------------------
# Each check is a function of a manifest in `manifest_checks.py`; here
# the repo's own goes through them case by case, and further down a
# grown copy goes through all of them.

def test_manifest_has_exactly_the_contract_keys():
    checks.contract_keys(MANIFEST)


def test_manifest_run_budget_fits_with_24_cells():
    checks.run_budget(MANIFEST)


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    checks.four_chip_share(MANIFEST)


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_units_and_lines_are_legal(section):
    checks.section_is_legal(MANIFEST, section)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell):
    checks.cell_reports(MANIFEST, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_by_name(cell):
    checks.cell_finds_its_files(MANIFEST, cell)


@pytest.mark.parametrize("metric", E2E)
def test_end_to_end_reader_exists(metric):
    checks.end_to_end_reader_exists(MANIFEST, metric)


@pytest.mark.parametrize("metric", LAYER)
def test_layer_reader_agrees_with_the_manifest(metric):
    """Every per-layer entry against its reader file and against what
    it held before (`manifest_checks.HELD`): its `moves`, `better`,
    `unit`, `source` and `layer`, and the cells it listed, which may
    have others beside them now. No entry is held to a place."""
    checks.layer_entry_agrees_with_its_reader(MANIFEST, metric)


def test_every_entry_of_today_is_held_and_a_list_that_shrinks_is_caught():
    assert set(checks.HELD) <= set(LAYER)
    shrunk = json.loads(json.dumps(MANIFEST))
    entry = next(m for m in shrunk["per_layer"] if m["name"] == "kv_read_amplification.tput")
    entry["workloads"].remove("doc_score_moe")
    with pytest.raises(AssertionError):
        checks.layer_entry_agrees_with_its_reader(shrunk, entry["name"])
    entry["workloads"] = ["doc_score_moe", "docqa_closed"]  # order is free
    checks.layer_entry_agrees_with_its_reader(shrunk, entry["name"])
    entry["moves"] = "itl_mean_ms"
    with pytest.raises(AssertionError):
        checks.layer_entry_agrees_with_its_reader(shrunk, entry["name"])


def test_layer_names_are_the_ones_perf_md_lists():
    checks.layer_names_are_in_perf_md(MANIFEST)


@pytest.mark.parametrize("name", checks.names(MANIFEST, "configs"))
def test_config_file_agrees_with_its_entry(name):
    checks.config_agrees_with_its_entry(MANIFEST, name)


def test_a_grown_copy_passes_every_manifest_check(tmp_path):
    """`benchmark/README.md`, "Adding a cell (no edit to any file
    here)": a copy grown as a `model_config` PR grows it (a
    configuration, a cell, two per-layer entries at the END, the cell's
    name at the end of every list it reports) goes through every check
    this directory makes of a manifest, and `manifest_diff` finds
    additions only."""
    from benchmark import manifest_diff

    root = checks.checkout(tmp_path)
    grown = checks.grow(root, "qwen2.5-3b", "docqa_closed")
    configs = checks.names(MANIFEST, "configs")
    assert checks.walk(MANIFEST) == (
        4 + 4 + 2 * len(CELLS) + len(E2E) + len(LAYER) + 2 * len(configs)
    )
    assert checks.walk(grown, root) == checks.walk(MANIFEST) + 2 + 2 + 2
    for name in ("stream_items_per_fetch.tput", "engine_ahead_share.tput",
                 "kv_read_amplification.tput", "serve_tokens_per_s"):
        section = "end_to_end" if name == "serve_tokens_per_s" else "per_layer"
        entry = next(m for m in grown[section] if m["name"] == name)
        assert entry["workloads"][-1] == "stub_cell"
    assert checks.names(grown, "per_layer")[-2:] == ["stub_requests", "stub_steps"]
    appended, problems = manifest_diff.diff(MANIFEST, grown)
    assert problems == [] and "workloads + stub_cell" in appended
    # and a check fails where the grown copy is wrong: the new cell's
    # entry names a reader that another layer owns
    grown["per_layer"][-1]["layer"] = "client"
    with pytest.raises(AssertionError):
        checks.walk(grown, root)


# -- configurations ---------------------------------------------------

PUBLISHED = {
    "qwen2.5-3b": dict(
        hidden_size=2048, num_hidden_layers=36, num_attention_heads=16,
        num_key_value_heads=2, intermediate_size=11008, vocab_size=151936,
        rope_theta=1000000.0, rms_norm_eps=1e-06,
    ),
    "mistral-7b-v0.3-l4": dict(
        hidden_size=4096, num_hidden_layers=32, num_attention_heads=32,
        num_key_value_heads=8, intermediate_size=14336, vocab_size=32768,
        rope_theta=1000000.0, rms_norm_eps=1e-05,
    ),
}
PUBLISHED["mistral-7b-v0.3-l8"] = PUBLISHED["mistral-7b-v0.3-l4"]


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_config_keeps_published_widths_and_lists_every_cut(name):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
    config = harness.load_config(MANIFEST, name)
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    changed = [k for k, v in PUBLISHED[name].items() if config[k] != v]
    assert changed == entry["reduced"]
    assert all(k == "num_hidden_layers" for k in changed)  # never a width
    for key in changed:
        assert config["reduced"][key]["published"] == PUBLISHED[name][key]
        assert config["reduced"][key]["here"] == config[key]
    model = config["model"]
    assert (
        model["dim"], model["n_layers"], model["n_heads"], model["n_kv_heads"],
        model["intermediate"], model["vocab_size"], model["rope_theta"],
        model["norm_eps"],
    ) == tuple(config[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "vocab_size",
        "rope_theta", "rms_norm_eps",
    ))
    assert config["assumed"] and config["deployment"]


# -- traffic ----------------------------------------------------------

def _rehearsal(name):
    return harness.apply_rehearsal(harness.load_traffic(name))


def test_quantile_lengths_are_a_fixed_sorted_multiset_within_bounds():
    spec = {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32, "max": 2048}
    a = lengths.quantile_lengths(spec, 101)
    assert a == sorted(a) and a[0] >= 32 and a[-1] <= 2048
    assert a[50] == 256  # the median is the median
    assert lengths.quantile_lengths({"dist": "uniform", "min": 0, "max": 100}, 4) == [12, 38, 62, 88]


@pytest.mark.parametrize("traffic", ["chat_open_loaded"])
def test_open_loop_repeats_from_a_seed_and_keeps_the_multiset(traffic):
    params = harness.load_traffic(traffic)
    a = serve_open.generate(params, 7, 20.0, 1000)
    b = serve_open.generate(params, 7, 20.0, 1000)
    c = serve_open.generate(params, 8, 20.0, 1000)
    assert a == b and a != c
    lead_in = params["lead_in_s"]
    n, n_lead = round(params["rate_per_s"] * 20.0), round(params["rate_per_s"] * lead_in)
    assert lead_in > 0 and len(a["requests"]) == len(c["requests"]) == n + n_lead
    due = [r["due_s"] for r in a["requests"]]
    assert due == sorted(due) and -lead_in <= due[0] and due[-1] < 20.0
    # the window's own count and lengths do not depend on the lead-in,
    # and each part keeps its multiset from seed to seed
    assert sum(1 for t in due if t < 0) == n_lead
    plain = serve_open.generate(dict(params, lead_in_s=0), 7, 20.0, 1000)
    assert plain["requests"] == a["requests"][n_lead:]
    for part in (slice(0, n_lead), slice(n_lead, None)):
        for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
            assert sorted(map(key, a["requests"][part])) == sorted(map(key, c["requests"][part]))
    # the seed draws the arrivals and deals the lengths
    assert due != [r["due_s"] for r in c["requests"]]
    assert [len(r["prompt"]) for r in a["requests"]] != [len(r["prompt"]) for r in c["requests"]]
    assert all(
        len(r["prompt"]) + r["max_new_tokens"] <= params["max_total_tokens"]
        and all(1 <= t < 1000 for t in r["prompt"])
        for r in a["requests"]
    )


def test_open_loop_arrivals_are_the_seeds_and_a_quarter_holds_what_the_draw_gives():
    # Poisson given the count: nothing evens out the quarters of a window,
    # and nothing keeps the long answers apart
    params = dict(harness.load_traffic("chat_open_loaded"), rate_per_s=1.0, lead_in_s=0)
    assert "stratum_s" not in params
    counts, tails = set(), set()
    for seed in range(8):
        requests = serve_open.generate(params, seed, 40.0, 1000)["requests"]
        assert len(requests) == 40
        quarters = [sum(1 for r in requests if 10 * j <= r["due_s"] < 10 * (j + 1)) for j in range(4)]
        assert sum(quarters) == 40
        counts.add(tuple(quarters))
        longest = max(requests, key=lambda r: r["max_new_tokens"])
        tails.add(int(longest["due_s"] // 10))
    assert len(counts) == 8 and any(max(q) - min(q) >= 4 for q in counts)
    assert len(tails) > 1  # where the longest answer falls is the seed's


@pytest.mark.parametrize("name,generator", [("chat_open_loaded", serve_open), ("docqa_closed", serve_closed)])
def test_the_drivers_warm_up_is_drawn_without_the_windows_requests(name, generator, monkeypatch):
    params = harness.load_traffic(name)
    made = generator.generate(params, 3, 10.0, 5000)
    assert set(made) == {"loop", "requests"} | ({"clients"} if generator.LOOP == "closed" else set())
    assert made["loop"] == generator.LOOP
    monkeypatch.setattr(generator, "generate", None)  # the driver's half never calls it
    warm = generator.warmup(params, 3, 5000)
    assert warm == generator.warmup(params, 3, 5000) != generator.warmup(params, 4, 5000)
    assert len(warm) == params["warmup_requests"]
    assert all(r["max_new_tokens"] == params["warmup_new_tokens"] for r in warm)
    window = made["requests"] if generator.LOOP == "open" else [next(made["requests"]) for _ in range(64)]
    assert not {tuple(r["prompt"]) for r in warm} & {tuple(r["prompt"]) for r in window}


def test_closed_loop_shares_documents_group_docs_apart():
    params = harness.load_traffic("docqa_closed")
    a = serve_closed.generate(params, 3, 10.0, 5000)
    b = serve_closed.generate(params, 3, 10.0, 5000)
    n, docs = 2 * params["group_docs"] * params["questions_per_doc"], params["group_docs"]
    ra = [next(a["requests"]) for _ in range(n)]
    rb = [next(b["requests"]) for _ in range(n)]
    assert ra == rb and a["clients"] == params["clients"]
    q = params["question_tokens"]
    for i, r in enumerate(ra[: n // 2]):
        doc = r["prompt"][:-q]
        assert params["document_tokens"]["min"] <= len(doc) <= params["document_tokens"]["max"]
        ask = i // docs
        assert r["shared_tokens"] == (len(doc) if ask else 0)
        if ask:
            assert ra[i - docs]["prompt"][:-q] == doc  # same document
            assert ra[i - docs]["prompt"] != r["prompt"]  # another question
    first, second = ra[: n // 2], ra[n // 2:]
    assert sorted(len(r["prompt"]) for r in first) == sorted(len(r["prompt"]) for r in second)
    assert first[0]["prompt"] != second[0]["prompt"]
    warm = {tuple(r["prompt"]) for r in serve_closed.warmup(params, 3, 5000)}
    assert not warm & {tuple(r["prompt"]) for r in ra}


def test_train_stream_repeats_from_a_seed():
    params = _rehearsal("stream_8k")
    a = train_stream.generate(params, 5, 4, 512)
    b = train_stream.generate(params, 5, 4, 512)
    c = train_stream.generate(params, 6, 4, 512)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert a["batch"] == 4 * params["sequences_per_chip"]
    assert a["tokens"].shape == (a["batch"] * params["max_steps"], params["seq_len"] + 1)
    assert a["tokens"].dtype == np.int32 and a["tokens"].max() < 512


# -- arithmetic -------------------------------------------------------

def test_a_cut_request_counts_what_it_streamed_and_a_failed_one_nothing():
    cut = {"ok": False, "cut": True, "due_s": 1.0, "sent_s": 1.0, "first_s": 1.5,
           "done_s": 2.6, "token_s": [1.5, 1.7, 2.3], "n_prompt": 30, "status": 200,
           "lead_in": False}
    waiting = {"ok": False, "cut": True, "due_s": 1.8, "sent_s": 1.8, "done_s": 2.6,
               "token_s": [], "n_prompt": 50, "status": 0}
    failed = {"ok": False, "cut": False, "due_s": 0.2, "sent_s": 0.2, "first_s": 0.3,
              "done_s": 0.4, "token_s": [0.3, 0.35], "n_prompt": 70, "status": 200}
    shed = {"ok": False, "cut": False, "due_s": 0.5, "sent_s": 0.5, "done_s": 0.6,
            "token_s": [], "n_prompt": 90, "status": 503}
    rows = [cut, waiting, failed, shed]
    assert [stats.served(r) for r in rows] == [True, True, False, False]
    # the one cut before its first token had waited 0.8 s by then
    assert stats.ttfts_ms(rows) == pytest.approx([500.0, 800.0])
    # only gaps that ended inside the window, only of served requests
    assert stats.pooled_gaps_ms(rows, 2.0) == pytest.approx([200.0])
    assert stats.pooled_gaps_ms(rows) == pytest.approx([200.0, 600.0])
    run = {"requests": rows, "window_s": 2.0, "loop": "open",
           "engine": {"before": {"prefix_tokens_saved": 5},
                      "after": {"prefix_tokens_saved": 20}}}
    read = lambda d, m: harness.load_module(d, m).reduce(run)
    assert read("end_to_end", "serve_tokens_per_s") == pytest.approx((30 + 2) / 2.0)
    assert read("end_to_end", "itl_mean_ms") == pytest.approx(200.0)
    assert read("layer_metrics", "shed_share") == pytest.approx(25.0)
    # saved tokens over the prompts whose first token had come, the
    # failed stream's too (the engine did prefill it): 15 of 30 + 70
    assert read("layer_metrics", "prefix_hit_token_share") == pytest.approx(15.0)


@pytest.mark.parametrize("q", [0, 25, 50, 90, 99, 100])
def test_percentile_is_numpys(q):
    values = list(np.random.default_rng(q).normal(size=57))
    assert stats.percentile(values, q) == pytest.approx(np.percentile(values, q))
    assert stats.percentile([], q) is None
    assert stats.percentile([3.0], q) == 3.0


def test_spread_lateness_and_gaps():
    # quartiles as `statistics.quantiles(values, n=4)` gives them
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx((4.5 - 1.5) / 3)
    six = [19.66, 20.29, 20.33, 20.49, 20.80, 21.04]
    assert stats.spread(six) == pytest.approx((20.86 - 20.1325) / 20.41)
    assert stats.spread([7.0]) is None and stats.spread([]) is None
    assert stats.lateness_ms([1.0, 2.0], [1.004, 1.9]) == pytest.approx([4.0, 0.0])
    assert stats.token_gaps_ms([0.0, 0.01, 0.04]) == pytest.approx([10.0, 30.0])


def test_end_to_end_readers_on_a_hand_made_run():
    requests = [
        {"ok": True, "due_s": 0.0, "sent_s": 0.0, "first_s": 0.1, "done_s": 0.5,
         "token_s": [0.1, 0.2, 0.5], "n_prompt": 10, "n_out": 3, "status": 200},
        {"ok": True, "due_s": 1.0, "sent_s": 1.1, "first_s": 1.4, "done_s": 2.5,
         "token_s": [1.4, 1.5], "n_prompt": 20, "n_out": 2, "status": 200},
    ]
    run = {"requests": requests, "window_s": 2.0, "loop": "open"}
    read = lambda d, m: harness.load_module(d, m).reduce(run)
    assert read("layer_metrics", "ttft_p50_ms") == pytest.approx(250.0)
    assert read("end_to_end", "itl_mean_ms") == pytest.approx(500 / 3)
    assert read("layer_metrics", "ttft_p90_ms") == pytest.approx(100 + 0.9 * 300)
    assert read("end_to_end", "itl_p95_ms") == pytest.approx(
        np.percentile([100, 300, 100], 95)
    )
    assert read("layer_metrics", "itl_p99_ms") == pytest.approx(
        np.percentile([100, 300, 100], 99)
    )
    # request 1 whole (10 + 3), request 2's prompt and its tokens by 2.0 s
    assert read("end_to_end", "serve_tokens_per_s") == pytest.approx((13 + 22) / 2.0)
    assert read("layer_metrics", "gen_late_p99_ms") == pytest.approx(99.0)
    assert read("layer_metrics", "shed_share") == 0.0
    run = {"steps": [[0.5, 500, 5, 5], [1.0, 500, 0, 10]], "tokens_per_step": 8192,
           "cell": {"chips": 1}}
    assert read("end_to_end", "train_tokens_per_s_chip") == pytest.approx(16384.0)
    assert read("layer_metrics", "data_wait_share") == pytest.approx(2.0)


def test_flops_against_a_hand_count():
    model = {"dim": 8, "n_layers": 2, "n_heads": 2, "n_kv_heads": 1,
             "intermediate": 16, "vocab_size": 100}
    # per layer: wq 8*8 + wk,wv 2*8*4 + wo 8*8 + mlp 3*8*16 = 576; head 800
    assert flops.matmul_params(model) == 2 * 576 + 800 == 1952
    # attention forward per token: layers * 2 matmuls * 2 * width 8 * (4+1)/2 keys
    assert flops.attention_flops_per_token_fwd(model, 4) == 2 * 2 * 2 * 8 * 2.5 == 160
    assert flops.train_flops_per_token(model, 4) == 3 * (2 * 1952 + 160)
    mistral = harness.load_config(MANIFEST, "mistral-7b-v0.3-l4")["model"]
    assert flops.matmul_params(mistral) == 4 * 218103808 + 4096 * 32768
    # the program's count has the embedding lookup in it; ours does not
    from ray_tpu.models.llama import LlamaConfig
    program = LlamaConfig(**mistral).num_params()
    assert program - flops.matmul_params(mistral) == 4096 * 32768 + 4 * 2 * 4096 + 4096


def test_peaks_name_their_source_and_refuse_an_unknown_chip():
    v5e = flops.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(flops.UnknownDevice):
        flops.peaks_for("cpu")
    with pytest.raises(flops.UnknownDevice):
        flops.peaks_for("_source")


# -- the load generator's senders -------------------------------------

@pytest.fixture
def token_server():
    """Streams `max_new_tokens` tokens, one every 10 ms, as the
    replica does: ASCII decimal and a trailing space."""
    import http.server
    import threading
    import time

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
            self.send_response(200)
            self.end_headers()
            try:
                for i in range(body["max_new_tokens"]):
                    self.wfile.write(f"{100 + i} ".encode())
                    self.wfile.flush()
                    time.sleep(0.01)
            except OSError:
                pass

        def log_message(self, *args):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    server.daemon_threads = True
    threading.Thread(target=server.serve_forever, daemon=True).start()
    yield server.server_address[1]
    server.shutdown()
    server.server_close()


def _clock():
    import time

    t0 = time.perf_counter()
    return lambda: time.perf_counter() - t0


def test_open_loop_sender_cuts_what_is_in_flight_after_the_drain(token_server):
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from benchmark.drivers import serve_client

    requests = [
        {"due_s": 0.0, "prompt": [1, 2, 3], "max_new_tokens": 5},
        {"due_s": 0.1, "prompt": [4, 5], "max_new_tokens": 10_000},
    ]
    clock, pool, stop = _clock(), ThreadPoolExecutor(4), threading.Event()
    rows = serve_client.offer_open(pool, token_server, requests, clock, stop)
    serve_client.finish(pool, rows, clock, 0.5, stop)
    done, endless = rows
    assert done["ok"] and not done["cut"] and done["tokens"] == [100, 101, 102, 103, 104]
    assert not endless["ok"] and endless["cut"] and endless["status"] == 200
    assert 0.5 <= endless["done_s"] < 1.5 and clock() < 2.0  # cut, not waited for
    n = endless["n_out"]
    assert 10 <= n < 10_000 and endless["tokens"] == list(range(100, 100 + n))
    assert len(endless["token_s"]) == n and endless["first_s"] >= 0.1
    assert all("sock" not in r for r in rows)


def test_closed_loop_callers_stop_at_the_windows_edge(token_server):
    import itertools
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from benchmark.drivers import serve_client

    stream = (
        {"prompt": [i], "max_new_tokens": 8, "shared_tokens": 0}
        for i in itertools.count()
    )
    clock, pool, stop = _clock(), ThreadPoolExecutor(8), threading.Event()
    rows = serve_client.offer_closed(pool, token_server, stream, 3, 0.6, clock, stop)
    import time

    time.sleep(max(0.0, 0.6 - clock()))
    serve_client.finish(pool, rows, clock, 0.6, stop)
    assert clock() < 1.5 and len(rows) >= 6
    assert all(r["ok"] or r["cut"] for r in rows) and sum(r["cut"] for r in rows) <= 3
    assert all(r["due_s"] == r["sent_s"] < 0.6 for r in rows)
    assert next(stream)["prompt"] == [len(rows)]  # nothing taken after the edge


# -- the load generator's process -------------------------------------

RECORD_KEYS = {
    "due_s", "sent_s", "done_s", "token_s", "tokens", "prompt", "n_prompt",
    "n_out", "want", "status", "ok", "cut",
}


def _client_window(port, traffic, seconds, tmp_path):
    """A window as the serve driver makes one: its records, when it
    opened, and how long the client's process took to hand them back."""
    import time

    from benchmark.drivers import serve

    with serve.ClientWindow(port, traffic, 11, seconds, 1000, str(tmp_path)) as window:
        window.open()
        assert abs(window.epoch - (time.time() - window.clock())) < 0.05
        rows = window.records()
        return rows, window.clock()


def test_client_process_offers_an_open_list_with_its_lead_in_and_cuts(token_server, tmp_path):
    from benchmark.drivers import serve, serve_client

    traffic = {
        "kind": "serve_open", "rate_per_s": 8.0, "lead_in_s": 0.5,
        "prompt_tokens": {"dist": "uniform", "min": 2, "max": 6},
        # 10 ms a token: the 8 quantile points run from 0.6 s to 19 s,
        # so some end inside the window, some in the drain, some are cut
        "output_tokens": {"dist": "uniform", "min": 2, "max": 2000},
        "max_total_tokens": 4096, "warmup_requests": 1, "warmup_new_tokens": 1,
    }
    seconds = 1.0
    rows, took = _client_window(token_server, traffic, seconds, tmp_path)
    assert took < seconds + serve_client.DRAIN_S + 2.0  # cut, not waited for
    want = serve_open.generate(traffic, 11, seconds, 1000)["requests"]
    assert len(rows) == len(want) == 12
    assert [r["due_s"] for r in rows] == [r["due_s"] for r in want]
    assert [(r["n_prompt"], r["want"]) for r in rows] == [
        (len(r["prompt"]), r["max_new_tokens"]) for r in want
    ]
    for r in rows:
        assert set(r) - {"first_s", "error"} == RECORD_KEYS | {"lead_in"}
        assert r["lead_in"] == (r["due_s"] < 0)
        assert r["status"] == 200 and (r["ok"] or r["cut"]) and r["ok"] != r["cut"]
        assert 0 <= r["sent_s"] - r["due_s"] < 0.25
        assert r["tokens"] == list(range(100, 100 + r["n_out"]))
        assert len(r["token_s"]) == r["n_out"] and r["first_s"] >= r["sent_s"]
    assert sum(r["lead_in"] for r in rows) == 4
    assert rows[0]["sent_s"] < 0 and any(r["ok"] for r in rows)
    cut = [r for r in rows if r["cut"]]
    assert cut and all(r["want"] > 400 and r["n_out"] < r["want"] for r in cut)
    assert all(r["done_s"] >= seconds + serve_client.DRAIN_S - 0.1 for r in cut)
    # the window's samples: its own requests, and gaps that ended in it
    assert len(stats.ttfts_ms(rows)) == len(stats.in_window(rows)) == 8
    gaps = stats.pooled_gaps_ms(rows, seconds)
    inside = sum(
        1 for r in rows for a, b in zip(r["token_s"], r["token_s"][1:])
        if 0 < b <= seconds
    )
    assert len(gaps) == inside > 100 and 9.0 < sum(gaps) / len(gaps) < 30.0
    point = serve.sweep_point(8.0, rows, seconds)
    assert (point["requests"], point["lead_in_requests"]) == (8, 4)
    assert point["in_flight_start"] >= 1 and point["late_p99_ms"] < 250.0


def test_client_process_drives_a_closed_list_to_the_edge(token_server, tmp_path):
    traffic = {
        "kind": "serve_closed", "clients": 3, "group_docs": 2, "questions_per_doc": 2,
        "document_tokens": {"dist": "uniform", "min": 4, "max": 8},
        "question_tokens": 2, "answer_tokens": {"dist": "uniform", "min": 4, "max": 8},
        "warmup_requests": 1, "warmup_new_tokens": 1,
    }
    seconds = 0.6
    rows, took = _client_window(token_server, traffic, seconds, tmp_path)
    assert took < seconds + 2.0 and len(rows) >= 6  # cut at the edge, no drain
    stream = serve_closed.generate(traffic, 11, seconds, 1000)["requests"]
    for r, request in zip(rows, stream):  # taken in the list's order
        assert set(r) - {"first_s", "error"} == RECORD_KEYS | {"shared_tokens"}
        assert (r["prompt"], r["n_prompt"], r["want"], r["shared_tokens"]) == (
            request["prompt"], len(request["prompt"]), request["max_new_tokens"],
            request["shared_tokens"],
        )  # the prompt itself: the served tokens are compared over it
        assert r["ok"] or r["cut"]
        assert 0 <= r["due_s"] == r["sent_s"] < seconds
    assert sum(r["cut"] for r in rows) <= 3
    run = {"requests": rows, "window_s": seconds, "loop": "closed"}
    rate = harness.load_module("end_to_end", "serve_tokens_per_s").reduce(run)
    assert rate == pytest.approx(sum(
        r["n_prompt"] * (0 < r.get("first_s", 9) <= seconds)
        + sum(1 for t in r["token_s"] if 0 < t <= seconds) for r in rows
    ) / seconds)


def test_a_lead_in_request_streams_into_the_window_and_is_no_sample_of_it():
    lead = {"ok": True, "cut": False, "lead_in": True, "due_s": -2.0, "sent_s": -1.9,
            "first_s": -1.5, "done_s": 0.6, "token_s": [-1.5, -0.1, 0.1, 0.5],
            "n_prompt": 40, "status": 200}
    own = {"ok": True, "cut": False, "lead_in": False, "due_s": 0.2, "sent_s": 0.21,
           "first_s": 0.5, "done_s": 0.9, "token_s": [0.5, 0.8], "n_prompt": 10,
           "status": 200}
    rows = [lead, own]
    assert stats.in_window(rows) == [own]
    assert stats.ttfts_ms(rows) == pytest.approx([300.0])
    # the gap that ended at -0.1 s is the lead-in's own; the one that
    # began before 0 and ended after it is the window's
    assert sorted(stats.pooled_gaps_ms(rows, 2.0)) == pytest.approx([200.0, 300.0, 400.0])
    run = {"requests": rows, "window_s": 2.0, "loop": "open"}
    read = lambda d, m: harness.load_module(d, m).reduce(run)
    assert read("layer_metrics", "gen_late_p99_ms") == pytest.approx(10.0)
    assert read("layer_metrics", "ttft_p50_ms") == pytest.approx(300.0)
    assert read("end_to_end", "itl_mean_ms") == pytest.approx(300.0)
    # the lead-in's prompt was prefilled before the window: 2 tokens of
    # it streamed inside, then the window's own 10 + 2
    assert read("end_to_end", "serve_tokens_per_s") == pytest.approx(14 / 2.0)


# -- the trace reduction ----------------------------------------------

def test_xplane_summary_of_a_hand_made_stream():
    ms = 1e6
    ops = [
        ["%while.1 = while(...)", 0 * ms, 10 * ms],       # container
        ["%fusion.1 = fusion(...)", 0 * ms, 4 * ms],
        ["%all-gather.2 = all-gather(...)", 4 * ms, 2 * ms],
        ["%fusion.2 = fusion(...)", 6 * ms, 4 * ms],
        ["%custom-call.7 = custom-call(...)", 15 * ms, 5 * ms],  # after a 5 ms gap
    ]
    host = [["python", "engine.step", 9 * ms, 7 * ms], ["python", "whole", 0, 100 * ms]]
    out = xplane.summarize({"device": {"/device:TPU:0": ops}, "host": host})
    assert out["planes"] == 1
    assert out["window_s"] == pytest.approx(0.020)
    assert out["busy_s"] == pytest.approx(0.015)
    assert out["collective_exposed_s"] == pytest.approx(0.002)
    assert dict(out["device_ops"])["fusion"] == pytest.approx(0.008)
    assert "while" not in dict(out["device_ops"])
    assert out["idle_gaps"][0] == ["engine.step", pytest.approx(0.005)]


def test_xplane_reduction_of_the_recorded_trace():
    path = os.path.join(ROOT, "benchmark", "trace", "recorded_v5e.json")
    with open(path) as f:
        recorded = json.load(f)
    out = xplane.summarize(recorded["events"])
    want = recorded["summary"]
    assert out["planes"] == want["planes"] >= 1
    for key in ("window_s", "busy_s", "collective_exposed_s"):
        assert out[key] == pytest.approx(want[key])
    assert 0 < out["busy_s"] <= out["window_s"]
    assert [n for n, _ in out["device_ops"]] == [n for n, _ in want["device_ops"]]


def test_xplane_reads_a_profile_written_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = xplane.read(xplane.find_xplane(str(tmp_path)))
    assert events["device"] == {}  # a CPU has no device plane
    assert any(name == "bench.step" for _, name, _, _ in events["host"])
    assert xplane.summarize(events) == {"planes": 0}


# -- the reference ----------------------------------------------------

@pytest.mark.parametrize("name", [c["name"] for c in MANIFEST["configs"]])
def test_every_configuration_resolves_to_a_reference_with_forward(name):
    checks.config_resolves_to_a_reference(MANIFEST, name)


def test_a_reference_that_is_not_there_or_has_no_forward_is_refused():
    from benchmark.reference import compare

    with pytest.raises(harness.BenchmarkError):
        compare.load("no_such_reference")
    with pytest.raises(harness.BenchmarkError):
        compare.load("compare")  # a module of the directory, but no reference


def test_reference_agrees_with_the_program_on_a_tiny_model():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import compare, llama_ref
    from ray_tpu.models.llama import LlamaConfig, forward, init_params

    model = dict(vocab_size=97, dim=32, n_layers=3, n_heads=4, n_kv_heads=2,
                 intermediate=48, rope_theta=1e6, max_seq_len=64,
                 norm_eps=1e-5, attn_bias=True)
    cfg = LlamaConfig(**model, dtype=jnp.float32, attention="reference")
    params = init_params(jax.random.PRNGKey(0), cfg)
    # biases are zero at init: make them count
    params["layers"]["bq"] = params["layers"]["bq"] + 0.3
    params["layers"]["bk"] = params["layers"]["bk"] - 0.2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (40,), 0, 97)
    got = forward(params, tokens[None], cfg)[0]
    want = llama_ref.forward(params, tokens, model, q_block=16)
    assert compare.relative_rms_error(got, want) < 1e-5
    # a path in lower precision must fail a bf16-sized tolerance's tenth
    low = forward(
        jax.tree.map(lambda x: x.astype(jnp.bfloat16), params), tokens[None],
        LlamaConfig(**model, dtype=jnp.bfloat16, attention="reference"),
    )[0]
    assert compare.relative_rms_error(low, want) > 1e-3


def test_the_int8_control_reads_above_the_programs_bf16_path_on_a_tiny_model():
    """`benchmark/control.py` at a size a test run can hold: the plain
    reference with int8 weights in the program's place. At the cells'
    own sizes it is run on the chip (PERF.md section 2 has the readings
    beside the limits); here it has to round to 8 bits and no further,
    and read further from the reference than the program's own bf16
    path does."""
    import jax
    import jax.numpy as jnp

    from benchmark import control
    from benchmark.reference import compare, llama_ref
    from ray_tpu.models.llama import LlamaConfig, forward, init_params

    w = jax.random.normal(jax.random.PRNGKey(3), (64, 16), jnp.float32)
    q = control.int8_matrix(w)
    scale = jnp.max(jnp.abs(w), axis=0) / 127.0
    levels = jnp.round(q / scale)
    assert float(jnp.max(jnp.abs(levels * scale - q))) < 1e-6  # on the int8 grid
    assert float(jnp.max(jnp.abs(levels))) == 127.0
    assert float(jnp.max(jnp.abs(q - w) / scale)) <= 0.5 + 1e-4  # rounded, not cut

    model = dict(vocab_size=97, dim=32, n_layers=3, n_heads=4, n_kv_heads=2,
                 intermediate=48, rope_theta=1e6, max_seq_len=64,
                 norm_eps=1e-5, attn_bias=True)
    cfg = LlamaConfig(**model, dtype=jnp.bfloat16, attention="reference")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (40,), 0, 97)
    want = llama_ref.forward(params, tokens, model, q_block=16)
    program = compare.relative_rms_error(forward(params, tokens[None], cfg)[0], want)
    untouched = {k: params[k] for k in ("embed", "final_norm")}
    quantized = control.int8_weights(jax.tree.map(jnp.copy, params))
    assert all(bool(jnp.all(quantized[k] == v)) for k, v in untouched.items())
    assert not bool(jnp.all(quantized["layers"]["wq"] == params["layers"]["wq"]))
    got = compare.relative_rms_error(llama_ref.forward(quantized, tokens, model, q_block=16), want)
    assert 1.5 * program < got < 0.2, (program, got)
    row = control.control_errors(
        {"model": model, "dtype": "bfloat16", "tolerance": {"logits_rel_rms": 0.035}}, [0], 40
    )[0]
    assert row["limit"] == 0.035 and 0 < row["all_positions"] < 0.2

