"""The load generator and stream reader: one thread, one event loop.

Sends each request when it is due (open loop: a slow server does not slow
the offered load), reads the server-sent events of `/v1/completions`, and
stamps every output token with its arrival on `time.monotonic()`. The
benchmark's tokenizer has one word to a token, so a chunk's words are its
tokens; the count is checked against the server's own `usage` at the end of
each stream.
"""

from __future__ import annotations

import asyncio
import json
import time

import aiohttp

from cellbench.server import id_of, text_of


def request_body(model: str, token_ids: list[int], max_tokens: int,
                 temperature: float, top_logprobs: int = 0) -> dict:
    body = {
        "model": model, "prompt": text_of(token_ids),
        "max_tokens": max_tokens, "stream": True, "temperature": temperature,
        "stream_options": {"include_usage": True},
        # fixed-length generation: random weights may sample the end token
        "ext": {"ignore_eos": True},
    }
    if top_logprobs:
        body["logprobs"] = top_logprobs
    return body


async def stream_one(session: aiohttp.ClientSession, url: str, body: dict,
                     rec: dict) -> None:
    """Fills `rec`: sent, tokens (arrival times), done, error, usage, and,
    where log-probs were asked for, `words` and `top` of each token."""
    rec["sent"] = time.monotonic()
    want = body["max_tokens"]
    try:
        async with session.post(url, json=body) as resp:
            if resp.status != 200:
                text = await resp.text()
                raise RuntimeError(f"HTTP {resp.status}: {text[:300]}")
            event = None
            async for raw in resp.content:
                now = time.monotonic()
                line = raw.decode("utf-8", "replace").rstrip("\r\n")
                if line.startswith("event:"):
                    event = line[6:].strip()
                    continue
                if not line.startswith("data: "):
                    continue
                data = line[6:]
                if data == "[DONE]":
                    break
                if event == "error":
                    raise RuntimeError(f"error frame: {data[:300]}")
                doc = json.loads(data)
                if doc.get("usage"):
                    rec["usage"] = doc["usage"]
                for choice in doc.get("choices") or []:
                    if choice.get("finish_reason"):
                        rec["finish"] = choice["finish_reason"]
                    n = len((choice.get("text") or "").split())
                    rec["tokens"].extend([now] * n)
                    lp = choice.get("logprobs")
                    if lp and lp.get("tokens"):
                        rec.setdefault("words", []).extend(lp["tokens"])
                        rec.setdefault("top", []).extend(lp["top_logprobs"])
        got = (rec.get("usage") or {}).get("completion_tokens")
        if got != want or len(rec["tokens"]) != want or rec.get("finish") != "length":
            raise RuntimeError(
                f"stream ended with {got} tokens by usage, {len(rec['tokens'])} "
                f"counted, finish {rec.get('finish')!r}; wanted {want}"
            )
        rec["done"] = True
    except asyncio.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 — reported as a failed request
        rec["error"] = f"{type(e).__name__}: {e}"


def new_record(req: dict, t0: float) -> dict:
    return {
        "index": req.get("index"), "due": t0 + req["due_s"], "sent": None,
        "tokens": [], "done": False, "error": None,
        "prompt_tokens": len(req["token_ids"]),
        "output_tokens": req["output_tokens"],
    }


async def offer(port: int, model: str, requests: list[dict], t0: float,
                stop_at: float | None, temperature: float,
                top_logprobs: int = 0, on_tick=None) -> list[dict]:
    """Send `requests` (each with `due_s`, `token_ids`, `output_tokens`) on
    their schedule counted from `t0`. With `stop_at`, streams still open
    then are cut (neither completed nor failed); without, wait for all.
    `on_tick(now, records)` is called about every 50 ms for whatever the
    caller has to do on the same clock (open the window, read a counter,
    count the live streams)."""
    url = f"http://127.0.0.1:{port}/v1/completions"
    records = [new_record(r, t0) for r in requests]
    timeout = aiohttp.ClientTimeout(total=None, sock_read=900)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:

        async def one(req, rec):
            delay = rec["due"] - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            body = request_body(
                model, req["token_ids"], req["output_tokens"], temperature,
                top_logprobs,
            )
            await stream_one(session, url, body, rec)

        tasks = [asyncio.create_task(one(q, r)) for q, r in zip(requests, records)]
        try:
            while True:
                now = time.monotonic()
                if on_tick is not None:
                    await on_tick(now, records)
                if stop_at is not None and now >= stop_at:
                    break
                if all(t.done() for t in tasks):
                    break
                await asyncio.sleep(0.05)
        finally:
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
    return records


def parse_top(rec: dict) -> tuple[list[int], list[list[int]], list[list[float]]]:
    """Generated token ids, and for each position the server's top ids and
    their log-probs, from a stream that asked for log-probs."""
    ids = [id_of(w) for w in rec["words"]]
    top_ids, top_lps = [], []
    for entry in rec["top"]:
        pairs = sorted(entry.items(), key=lambda kv: -kv[1])
        top_ids.append([id_of(w) for w, _ in pairs])
        top_lps.append([float(lp) for _, lp in pairs])
    return ids, top_ids, top_lps
