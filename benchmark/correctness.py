"""The comparison that decides `correct`.

Outside the window, what the system serves is held to the benchmark's own
float32 reference (reference.py) over the engine's own weights. Inside it,
every request must have done what was asked and the kernels must have done the
work.
"""

from __future__ import annotations

import http.client
import json

import numpy as np

from benchmark import reduce, reference, trafficgen

# Served tokens against the float32 forward, as a share of a row's max |logit|:
# int8 x int8 dots, int8 KV and bf16 activations over 36 layers on one side,
# float32 on the other. PR 21's v5e runs showed 0.0151 at worst on Llama-3.1-8B
# (32 layers, same kernels); this model showed 0.0 in four of six prompts and
# 0.016 and 0.028 in the others, the same in both runs of each (PR 23, v5e).
# Another request's logits or a lost KV row miss by the spread of the logits
# themselves (0.5 and more). About three times the worst seen.
SERVED_TOL_REL = 0.08
# Cosine distance between a served embedding and the reference's. The vector
# is one hidden state after 36 int8 x bf16 layers, cut to `dimensions` and
# normalised again; the v5e showed 0.0009-0.00125 over 28 inputs of 14 runs
# (PR 23), and the two inputs of a run themselves lie 0.24-0.33 apart: another
# input's vector, or a layer left out, misses by far. Four times the worst seen.
EMBED_TOL_COS = 0.005
PAD_TO = 128
REF_PROMPT_BYTES = 72
REF_TOKENS = 8


def _post(port: int, path: str, body: dict, timeout: float = 300.0) -> tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(body), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def served_tokens(gen, port: int, model: str, prompt: str) -> tuple[list[int], list[int]]:
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
            "model": model, "stream": False, "max_tokens": REF_TOKENS, "temperature": 0.0,
            "messages": [{"role": "user", "content": prompt}]})
    finally:
        del gen._process_token
    if status != 200:
        raise AssertionError(f"reference request: HTTP {status}: {raw[:200]!r}")
    if len(served) != 1:
        raise AssertionError(f"reference request: {len(served)} requests were served, want 1")
    (prompt_ids, emitted), = served.values()
    return prompt_ids, emitted


def hold_to_reference(gen, prompt_ids: list[int], emitted: list[int]) -> dict:
    """Every served token must be the float32 forward's greedy choice among
    the tokens the engine may emit, or lie within SERVED_TOL_REL of that
    choice's logit (of the row's max |logit|); teacher-forced, one pass."""
    if not emitted:
        raise AssertionError("reference request: nothing was served")
    mask = gen._allowed_mask
    allowed = np.arange(gen.cfg.vocab_size) if mask is None else np.flatnonzero(np.asarray(mask))
    seq = prompt_ids + emitted[:-1]
    rows = np.arange(len(prompt_ids) - 1, len(seq))
    seq = np.asarray(seq + [0] * (-len(seq) % PAD_TO), np.int32)
    ref = reference.logits(gen.cfg, gen.params, seq, rows, allowed)
    worst, own = 0.0, 0
    for k, tok in enumerate(emitted):
        col = np.flatnonzero(allowed == tok)
        if not len(col):
            raise AssertionError(f"served token {tok} at step {k} is not one the engine may emit")
        scale = float(np.max(np.abs(ref[k]))) or 1.0
        regret = float(np.max(ref[k]) - ref[k, col[0]])
        if not np.isfinite(ref[k]).all() or regret > SERVED_TOL_REL * scale:
            raise AssertionError(
                f"served token {tok} at step {k} is {regret:.4g} under the reference's choice "
                f"(row max |logit| {scale:.3g}, tolerance {SERVED_TOL_REL * scale:.3g})")
        worst = max(worst, regret / scale)
        own += regret == 0.0
    return {"served_tokens": len(emitted), "reference_own_choice": own,
            "worst_regret_rel": worst, "prompt_tokens": len(prompt_ids)}


def check_generation(run: dict) -> dict:
    sut = run["sut"]
    gen = sut["gen"]
    seed = int(run["args"].seed)
    prompt = trafficgen.text(REF_PROMPT_BYTES, seed, "ref")  # another prompt for every seed
    prompt_ids, emitted = served_tokens(gen, sut["port"], sut["model"], prompt)
    notes = hold_to_reference(gen, prompt_ids, emitted)
    falls = run["end"]["reference_falls"]
    notes.update(attn_impl=gen.attn_impl, decode_impl=gen.decode_impl,
                 reference_falls=sum(falls.values()) if falls else 0)
    want = run["spec"]["config"]["program"].get("expect", {})
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
        want = reference.pooled(emb.cfg, emb.params, seq, len(ids), dims)
        if vec.shape != want.shape or not np.isfinite(vec).all():
            raise AssertionError(f"served vector {vec.shape}, reference {want.shape}, or non-finite")
        dist.append(1.0 - float(np.dot(vec, want) / (np.linalg.norm(vec) * np.linalg.norm(want))))
    apart = 1.0 - float(np.dot(served[0], served[1]))
    if max(dist) > EMBED_TOL_COS:
        raise AssertionError(f"served embeddings lie {dist} (cosine distance) from the reference, "
                             f"tolerance {EMBED_TOL_COS}")
    return {"cosine_distance_to_reference": dist, "two_inputs_apart": apart,
            "tolerance": EMBED_TOL_COS}


def check(run: dict) -> dict:
    """{"correct": bool, "notes": {...}}. A comparison that fails raises: the
    run then prints no result line at all."""
    bad = [r for r in run["records"] if not reduce.ok(r)]
    notes = check_generation(run) if run["sut"]["gen"] is not None else check_embedding(run)
    notes["requests_failed"] = len(bad)
    if bad:
        notes["first_failure"] = {k: bad[0].get(k) for k in ("i", "status", "error", "finish")}
    return {"correct": not bad, "notes": notes}
