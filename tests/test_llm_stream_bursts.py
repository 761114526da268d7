"""One stream item per request per engine step: the SSE frames a decode
window made for a request leave the replica's handler as one `str`, the
body stays the per-token sequence of frames, and
`ray_tpu.request.stream_done` says how many tokens an item carried.

The seeded tiny model speaks bytes under the bundled byte-level tokenizer:
a token that leaves a character incomplete makes no frame of its own, so
the tests count frames with the decoder the server uses."""

import json
import math
import time

import pytest

from ray_tpu._private import flight_recorder as fr

DECODE_STEPS = 16
# These requests run one at a time in two slots: a slot is free, so the
# engine runs half windows.
WINDOW = DECODE_STEPS // 2
CONFIG = {"model": "tiny", "model_id": "tiny-bursts", "seed": 7,
          "model_config": {"vocab_size": 300},
          "engine_config": {"max_seqs": 2, "page_size": 4,
                            "max_pages_per_seq": 64,
                            "decode_steps": DECODE_STEPS}}
BODIES = {
    "completions": {"prompt": "the quick brown fox"},
    "chat": {"messages": [{"role": "user", "content": "hello there"}]},
}


@pytest.fixture(scope="module")
def app():
    from ray_tpu.llm._internal.openai import OpenAIServer

    server = OpenAIServer(CONFIG)
    yield server
    server.server._running = False


def _suffix(api):
    return "/chat/completions" if api == "chat" else "/completions"


def _stream(app, api, **body):
    """(str items, every item) of one streamed request."""
    items = list(app({"suffix": _suffix(api),
                      "body": {**BODIES[api], "stream": True, **body}}))
    assert items[0] == {"__http__": {"content_type": "text/event-stream"}}
    assert all(isinstance(i, str) for i in items[1:])
    return items[1:], items


def _prompt_ids(app, api):
    return (app._chat_ids(BODIES[api]) if api == "chat"
            else app._prompt_ids(BODIES[api]))


def _frames(text):
    """A body's frames, without what differs between two requests."""
    assert text.endswith("\n\n")
    out = []
    for frame in text[:-2].split("\n\n"):
        assert frame.startswith("data: ")
        if frame == "data: [DONE]":
            out.append(frame)
            continue
        obj = json.loads(frame[6:])
        obj.pop("id"), obj.pop("created")
        out.append(obj)
    return out


def _per_token_frames(app, api, tokens, stops=()):
    """The frames the per-token path made of `tokens`: one per non-empty
    delta, the stop matcher token by token, then the closing frames."""
    from ray_tpu.llm._internal.openai import (
        _IncrementalDecoder,
        _StopMatcher,
    )

    chat = api == "chat"
    obj = "chat.completion.chunk" if chat else "text_completion"

    def frame(choice):
        return {"object": obj, "model": app.model_id,
                "choices": [{"index": 0, **choice}]}

    def text_frame(text):
        return frame({"delta": {"content": text}, "finish_reason": None}
                     if chat else {"text": text, "finish_reason": None})

    out = []
    if chat:
        out.append(frame({"delta": {"role": "assistant", "content": ""},
                          "finish_reason": None}))
    dec, matcher = _IncrementalDecoder(app.tokenizer), _StopMatcher(
        list(stops))
    stopped = False
    for tok in tokens:
        delta = dec.push(tok)
        if stops:
            delta, stopped = matcher.push(delta)
        if delta:
            out.append(text_frame(delta))
        if stopped:
            break
    if stops and not stopped:
        tail = matcher.flush()
        if tail:
            out.append(text_frame(tail))
    out.append(frame({"delta": {}, "finish_reason": "stop"} if chat
                     else {"text": "", "finish_reason": "stop"}))
    out.append("data: [DONE]")
    return out


def _visible(app, tokens):
    """How many of `tokens` make a frame of their own."""
    from ray_tpu.llm._internal.openai import _IncrementalDecoder

    dec = _IncrementalDecoder(app.tokenizer)
    return sum(1 for t in tokens if dec.push(t))


def _stream_done():
    return [e["args"] for e in fr.dump_events()
            if e.get("name") == "ray_tpu.request.stream_done"]


def _text(frames):
    return "".join(
        (c["choices"][0].get("text") or
         c["choices"][0].get("delta", {}).get("content") or "")
        for c in frames if isinstance(c, dict))


@pytest.mark.parametrize("api", ["completions", "chat"])
def test_body_is_the_per_token_sequence_of_frames(app, api):
    n = 29
    strs, _ = _stream(app, api, max_tokens=n)
    tokens = app.server.generate_all(_prompt_ids(app, api),
                                     max_tokens=n)["tokens"]
    assert len(tokens) == n
    want = _per_token_frames(app, api, tokens)
    assert _frames("".join(strs)) == want
    # a frame per token that completes a character: none merged or dropped
    assert len(want) == _visible(app, tokens) + (3 if api == "chat" else 2)


@pytest.mark.parametrize("api", ["completions", "chat"])
@pytest.mark.parametrize("n", [1, 9, 32])
def test_one_item_per_engine_step(app, api, n):
    strs, items = _stream(app, api, max_tokens=n)
    assert len(items) <= 1 + math.ceil((n - 1) / WINDOW) + 3
    tokens = app.server.generate_all(_prompt_ids(app, api),
                                     max_tokens=n)["tokens"]
    # The first token is a step of its own (the prefill's), and so an item
    # of its own; the frames of a window travel together, a step that made
    # no frame makes no item, and the closing frames ride with the last.
    steps = [tokens[:1]] + [tokens[i:i + WINDOW]
                            for i in range(1, n, WINDOW)]
    want = [_visible(app, tokens[:1])]
    seen = 1
    for step in steps[1:]:
        seen += len(step)
        want.append(_visible(app, tokens[:seen]) - sum(want))
    want[-1] += 2
    if n > 1:
        assert want[0] == 1 and max(want) > 2
    per_item = [len(_frames(s)) for s in strs]
    if api == "chat":
        assert per_item[0] == 1 and not _text(_frames(strs[0]))
        per_item = per_item[1:]
    assert per_item == [w for w in want if w]
    assert _frames(strs[-1])[-2:] == _per_token_frames(app, api, [])[-2:]


@pytest.mark.parametrize("api", ["completions", "chat"])
def test_stop_string_inside_a_burst(app, api):
    from ray_tpu.llm._internal.openai import _IncrementalDecoder

    n = 60
    tokens = app.server.generate_all(_prompt_ids(app, api),
                                     max_tokens=n)["tokens"]
    dec = _IncrementalDecoder(app.tokenizer)
    deltas = [dec.push(t) for t in tokens]
    text = "".join(deltas)
    # Two characters that two tokens in the middle of one decode window
    # make, and that the text does not hold earlier: the match falls inside
    # a burst, with tokens of the same step behind it.
    at = next(
        i for i in range(1 + WINDOW, n - WINDOW)
        if 1 <= (i - 1) % WINDOW <= WINDOW - 4
        and len(deltas[i]) == 1 and len(deltas[i + 1]) == 1
        and text.find(deltas[i] + deltas[i + 1]) == len("".join(deltas[:i])))
    stop = deltas[at] + deltas[at + 1]
    strs, _ = _stream(app, api, max_tokens=n, stop=stop)
    got = _frames("".join(strs))
    assert got == _per_token_frames(app, api, tokens, stops=[stop])
    assert _text(got) == text[:text.find(stop)]
    # nothing after the match but the closing frames: the handler read no
    # token behind it, though the step had made some; and the slot is free
    assert got[-1] == "data: [DONE]" and got[-2]["choices"][0][
        "finish_reason"] == "stop"
    done = _stream_done()[-1]
    assert done["tokens"] == at + 2 and "lag_ms" not in done
    deadline = time.monotonic() + 30
    while app.stats()["running"] and time.monotonic() < deadline:
        time.sleep(0.01)
    assert app.stats()["running"] == 0 and app.stats()["waiting"] == 0


def test_generate_yields_one_dict_per_token(app):
    n = 1 + 2 * WINDOW + 3
    items = list(app.server.generate([5, 17, 42], max_tokens=n))
    assert len(items) == n
    assert all(isinstance(i["token"], int) for i in items)
    # the first dict names the request, and the engine's `first_token`
    # mark under that name times its first token, once
    assert "rid" in items[0] and not any("rid" in i for i in items[1:])
    (first,) = [e["args"] for e in fr.dump_events()
                if e.get("name") == "ray_tpu.request.first_token"
                and e["args"]["rid"] == items[0]["rid"]]
    assert first["queue_ms"] >= 0 and first["prefill_ms"] > 0
    # `more` counts down inside a step: the prefill's token alone, then
    # whole windows, then what was left of the last one
    assert [i["more"] for i in items] == (
        [0] + 2 * list(range(WINDOW - 1, -1, -1)) + [2, 1, 0])
    assert "delivered_s" in items[-1]
    assert not any("delivered_s" in i for i in items[:-1])
    assert [i["token"] for i in items] == app.server.generate_all(
        [5, 17, 42], max_tokens=n)["tokens"]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_decoder_emits_what_decoding_everything_again_would(seed):
    """`_IncrementalDecoder` decodes only what it holds back; its deltas
    are those of decoding the whole answer at every token, which it did."""
    import random

    from ray_tpu.llm import ByteBPETokenizer
    from ray_tpu.llm._internal.openai import _IncrementalDecoder

    tok = ByteBPETokenizer.byte_fallback()
    rng = random.Random(seed)
    whole = tok.encode("héllo — ✓ 漢字 😀 abc")
    ids = [rng.choice(whole) if rng.random() < 0.5
           else rng.randrange(tok.vocab_size) for _ in range(400)]
    dec, seen, emitted = _IncrementalDecoder(tok), [], 0
    for n, i in enumerate(ids):
        seen.append(i)
        text = tok.decode(seen)
        want = "" if text.endswith("\ufffd") else text[emitted:]
        emitted += len(want)
        assert dec.push(i) == want, n
        assert not (want and dec._ids)  # nothing emitted is kept


def test_engine_error_reaches_every_open_stream(monkeypatch):
    from ray_tpu.llm._internal.openai import OpenAIServer

    app = OpenAIServer(CONFIG)
    try:
        streams = [app({"suffix": _suffix(api),
                        "body": {**BODIES[api], "stream": True,
                                 "max_tokens": 90}})
                   for api in ("completions", "chat")]
        for s in streams:  # up to the first token: both requests are open
            assert "__http__" in next(s)
            assert next(s).startswith("data: ")

        def boom():
            raise ValueError("device lost")

        monkeypatch.setattr(app.server.engine, "step", boom)
        for s in streams:
            with pytest.raises(RuntimeError, match="engine failed: device"):
                list(s)
    finally:
        app.server._running = False


@pytest.mark.parametrize("api", ["completions", "chat"])
def test_stream_done_mark(app, api):
    n = 32
    _, items = _stream(app, api, max_tokens=n)
    args = _stream_done()[-1]
    assert args["tokens"] == n and args["items"] == len(items)
    assert args["tokens_per_item"] == pytest.approx(n / len(items))
    assert args["tokens_per_item"] > 1
    assert 0 <= args["lag_ms"] < 5000
    # the engine's own id of the request, as its other marks carry it
    first = [e["args"]["rid"] for e in fr.dump_events()
             if e.get("name") == "ray_tpu.request.first_token"]
    assert args["rid"] in first
