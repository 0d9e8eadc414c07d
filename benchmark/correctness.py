"""The comparison that decides `correct`.

Outside the window, what the system serves is held to a plain float32
reference over the engine's own weights. Inside it, every request must have
done what was asked and the kernels must have done the work.

**A configuration brings its own reference, and with it the table that holds
its file.** A configuration's file may hold `"reference": "<name>"`, which names
`benchmark/references/<name>.py`; without the key it is `benchmark/reference.py`
(the dense GQA decoder family). A later PR adds a family as files: nothing here
is edited. A reference module holds:

- `check(cfg)`: raises `NotImplementedError` for a `ModelConfig` whose
  equations it does not cover. run.py calls it before the engine is built, so a
  configuration with no reference fails in seconds and not after a window.
- `logits(cfg, params, tokens, rows, cols) -> float32 [len(rows), len(cols)]`
  for a generation configuration: row t is the distribution over token t+1 of
  one unbatched, padded sequence, cut to the token ids `cols`;
  `pooled(cfg, params, tokens, length, dimensions) -> float32 [width]` for an
  embedding one. `params` is the engine's own tree (int8 `{"q", "s"}` leaves
  and plain ones); everything else comes from `cfg` and the seed's tokens.
- `SERVED_TOL_REL` beside `logits`, `EMBED_TOL_COS` beside `pooled`: the
  tolerance, with the readings it was set from written beside it.
- optionally three tables, keyed by the dotted path of a key in the file
  (`linear_attn_config.head_dim`, `rope_parameters.rope_theta`; a list is one
  value): `HELD`, path -> a function of the program's `ModelConfig` that returns
  what the program will compute with (number, bool, string or list); `ONLY`,
  path -> the one value for which the program's behaviour is the published one
  (any other value in the file is refused); `STATED`, path -> one line saying
  why the key changes nothing the program computes (an empty reason is refused;
  run.py prints these paths in every run's log).

**Every key of the file is held.** The harness's own keys are `run.HARNESS_KEYS`
(`name`, `source`, `reference`, `reference_request`, `reduced`, `published`,
`assumed`, `deployment`, `weights_seed`, `program`); every other key is the
model's. `run.check_sizes` walks them at any depth, before the engine is built
and again on the engine's own `cfg`: each number, bool, string and list must
equal what run.py's own tables (`MODEL_KEYS`, `DERIVED_KEYS`, `ONLY_VALUE`,
`ROPE_KEYS`, `STATED_NOT_HELD`) or the module's give for it, null reading as 0.
A path no table knows stops the run; a path both run.py and the module hold
stops it when the module is loaded, with one exception: a path of
`ONLY_VALUE` (`moe_layer_freq`, `n_group`, `topk_group`: one behaviour, any
other value refused) may be named in a module's `HELD`, and only there. A
family that brings the behaviour holds the key to what its program computes
with, and the file is then compared with that holder and not with
`ONLY_VALUE`; in `ONLY` or `STATED` the path is refused as any other overlap
is, and a configuration whose module does not hold it is compared with
`ONLY_VALUE` as before. **A cut is stated beside what was
published**: `published` is a group with the source's value of exactly the
paths in `reduced` (`{"num_hidden_layers": 48, "n_routed_experts": 320}`), and
the module's tables may hold `published.<path>` too (a router keeps the
published width while `n_routed_experts` counts the experts held here). A file
with `reduced: []` has no `published`. `benchmark/check_source.py` compares a
file with its row of the `model-configs` catalog as the driver will; run.py
never calls it, because the catalog is not on the measuring machine.

A configuration may also state the request the comparison serves,
`"reference_request": {"prompt_bytes": n, "tokens": m}`: a cell whose work is
a long cache needs a comparison that crosses KV blocks. Without the key it is
72 bytes and 8 tokens. Every run's `checks` name the reference and the
tolerance it was held to. run.py loads the module once, before the engine is
built (`run.load_reference`), and hands it over as `run["sut"]["reference"]`.
"""

from __future__ import annotations

import http.client
import json

import numpy as np

from benchmark import reduce, trafficgen

PAD_TO = 128
REF_REQUEST = {"prompt_bytes": 72, "tokens": 8}


def reference_request(config: dict, max_seq_len: int) -> tuple[int, int]:
    """(prompt bytes, tokens to serve) of the comparison's one request. The
    padded sequence has to fit the positions the engine serves."""
    req = dict(REF_REQUEST, **config.get("reference_request", {}))
    if set(req) != set(REF_REQUEST):
        raise AssertionError(f"reference_request holds {sorted(req)}, want {sorted(REF_REQUEST)}")
    n, m = int(req["prompt_bytes"]), int(req["tokens"])
    # the chat template adds some tens of tokens to the prompt's bytes: the
    # exact length is asserted in hold_to_reference, this one before the run
    if not (n > 0 and m > 0 and -(-(n + m) // PAD_TO) * PAD_TO < max_seq_len):
        raise AssertionError(f"reference_request {req} does not fit under {max_seq_len} positions")
    return n, m


def _post(port: int, path: str, body: dict, timeout: float = 300.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def served_tokens(gen, port: int, model: str, prompt: str,
                  n_tokens: int) -> tuple[list[int], list[int]]:
    """One greedy chat over HTTP, and the token ids the engine emitted for it.
    HTTP carries text, and the byte tokenizer's text does not give the ids
    back, so they are read where the engine's loop emits them (chip_smoke.py's
    tap), for this one request only."""
    served: dict[str, tuple[list[int], list[int]]] = {}
    emit = gen._process_token

    def tap(slot, tok, pos):
        served.setdefault(slot.req.request_id, (list(slot.req.prompt_ids), []))[1].append(int(tok))
        return emit(slot, tok, pos)

    gen._process_token = tap
    try:
        status, raw = _post(port, "/v1/chat/completions", {
            "model": model, "stream": False, "max_tokens": n_tokens, "temperature": 0.0,
            "messages": [{"role": "user", "content": prompt}]})
    finally:
        del gen._process_token
    if status != 200:
        raise AssertionError(f"reference request: HTTP {status}: {raw[:200]!r}")
    if len(served) != 1:
        raise AssertionError(f"reference request: {len(served)} requests were served, want 1")
    (prompt_ids, emitted), = served.values()
    return prompt_ids, emitted


def hold_to_reference(module, gen, prompt_ids: list[int], emitted: list[int]) -> dict:
    """Every served token must be the float32 forward's greedy choice among
    the tokens the engine may emit, or lie within the reference module's
    SERVED_TOL_REL of that choice's logit (of the row's max |logit|);
    teacher-forced, one pass."""
    if not emitted:
        raise AssertionError("reference request: nothing was served")
    tol = float(module.SERVED_TOL_REL)
    mask = gen._allowed_mask
    allowed = np.arange(gen.cfg.vocab_size) if mask is None else np.flatnonzero(np.asarray(mask))
    seq = prompt_ids + emitted[:-1]
    rows = np.arange(len(prompt_ids) - 1, len(seq))
    seq = np.asarray(seq + [0] * (-len(seq) % PAD_TO), np.int32)
    if len(seq) >= gen.max_seq_len:
        raise AssertionError(f"the padded reference sequence, {len(seq)} tokens, does not fit "
                             f"under the {gen.max_seq_len} positions served")
    ref = module.logits(gen.cfg, gen.params, seq, rows, allowed)
    worst, own = 0.0, 0
    for k, tok in enumerate(emitted):
        col = np.flatnonzero(allowed == tok)
        if not len(col):
            raise AssertionError(f"served token {tok} at step {k} is not one the engine may emit")
        scale = float(np.max(np.abs(ref[k]))) or 1.0
        regret = float(np.max(ref[k]) - ref[k, col[0]])
        if not np.isfinite(ref[k]).all() or regret > tol * scale:
            raise AssertionError(
                f"served token {tok} at step {k} is {regret:.4g} under the reference's choice "
                f"(row max |logit| {scale:.3g}, tolerance {tol * scale:.3g})")
        worst = max(worst, regret / scale)
        own += regret == 0.0
    return {"served_tokens": len(emitted), "reference_own_choice": own,
            "worst_regret_rel": worst, "prompt_tokens": len(prompt_ids), "tolerance": tol}


def check_generation(run: dict) -> dict:
    sut = run["sut"]
    gen = sut["gen"]
    seed = int(run["args"].seed)
    config = run["spec"]["config"]
    name, module = sut["reference"]
    n_bytes, n_tokens = reference_request(config, gen.max_seq_len)
    prompt = trafficgen.text(n_bytes, seed, "ref")  # another prompt for every seed
    prompt_ids, emitted = served_tokens(gen, sut["port"], sut["model"], prompt, n_tokens)
    notes = dict(hold_to_reference(module, gen, prompt_ids, emitted), reference=name)
    falls = run["end"]["reference_falls"]
    notes.update(attn_impl=gen.attn_impl, decode_impl=gen.decode_impl,
                 reference_falls=sum(falls.values()) if falls else 0)
    want = config["program"].get("expect", {})
    for key, value in want.items():
        if getattr(gen, key) != value:
            raise AssertionError(f"{key}={getattr(gen, key)!r}, the configuration states {value!r}")
    if notes["reference_falls"]:
        raise AssertionError(f"kernels fell to reference math: {falls}")
    return notes


def check_embedding(run: dict) -> dict:
    sut = run["sut"]
    emb = sut["emb"]
    seed = int(run["args"].seed)
    name, module = sut["reference"]
    tol = float(module.EMBED_TOL_COS)
    dims = int(run["spec"]["traffic"].get("dimensions", 0))
    texts = [trafficgen.text(n, seed + k, f"ref{k}") for k, n in enumerate((100, 120))]
    body: dict = {"model": sut["model"], "input": texts}
    if dims:
        body["dimensions"] = dims
    status, raw = _post(sut["port"], "/v1/embeddings", body)
    if status != 200:
        raise AssertionError(f"reference embeddings: HTTP {status}: {raw[:200]!r}")
    served = [np.asarray(row["embedding"], np.float32) for row in json.loads(raw)["data"]]
    dist = []
    for text, vec in zip(texts, served):
        ids = emb.prepare_ids(text)
        seq = np.asarray(ids + [0] * (-len(ids) % PAD_TO), np.int32)
        want = module.pooled(emb.cfg, emb.params, seq, len(ids), dims)
        if vec.shape != want.shape or not np.isfinite(vec).all():
            raise AssertionError(f"served vector {vec.shape}, reference {want.shape}, or non-finite")
        dist.append(1.0 - float(np.dot(vec, want) / (np.linalg.norm(vec) * np.linalg.norm(want))))
    apart = 1.0 - float(np.dot(served[0], served[1]))
    if max(dist) > tol:
        raise AssertionError(f"served embeddings lie {dist} (cosine distance) from the reference, "
                             f"tolerance {tol}")
    return {"cosine_distance_to_reference": dist, "two_inputs_apart": apart,
            "tolerance": tol, "reference": name}


def check(run: dict) -> dict:
    """{"correct": bool, "notes": {...}}. A comparison that fails raises: the
    run then prints no result line at all."""
    bad = [r for r in run["records"] if not reduce.ok(r)]
    notes = check_generation(run) if run["sut"]["gen"] is not None else check_embedding(run)
    notes["requests_failed"] = len(bad)
    if bad:
        notes["first_failure"] = {k: bad[0].get(k) for k in ("i", "status", "error", "finish")}
    return {"correct": not bad, "notes": notes}
