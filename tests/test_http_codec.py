"""The job submit's edge codec: a register body decoded to its `Job` in
one pass (`structs/codec.py from_json`) and the reply written in one
buffer (`agent/http.py`). Held to the generic path they replace: the
decode to `from_wire(from_json_tree(json.loads(raw)))`, the reply to what
`send_response` + `send_header` + `end_headers` + a second write send."""
import dataclasses
import io
import json
import socket

import pytest

from nomad_tpu import mock
from nomad_tpu.agent import Agent, AgentConfig
from nomad_tpu.agent.http import HttpError, JobBody
from nomad_tpu.lib.metrics import default_registry
from nomad_tpu.structs import (EphemeralDisk, Job, RequestedDevice,
                               Resources)
from nomad_tpu.structs.codec import (from_json, from_json_tree, from_wire,
                                     to_json_tree, to_wire)
from nomad_tpu.structs.job import Affinity, Constraint, Spread, SpreadTarget


def _three_steps(raw):
    return from_wire(from_json_tree(json.loads(raw)))


def _typed(x):
    """`x` as nested tuples that carry every node's type and, for dicts,
    key order: equal only where the two trees match field for field."""
    if dataclasses.is_dataclass(x):
        return (type(x), tuple((f.name, _typed(getattr(x, f.name)))
                               for f in dataclasses.fields(x)))
    if isinstance(x, dict):
        return (dict, tuple((k, _typed(v)) for k, v in x.items()))
    if isinstance(x, list):
        return (list, tuple(_typed(v) for v in x))
    return (type(x), x)


def _body(job) -> bytes:
    """A register body as the SDK sends it."""
    return json.dumps(to_json_tree({"job": to_wire(job)})).encode()


def _job(**kw):
    job = mock.job(**kw)
    job.id = job.name = "edge-codec"
    return job


def _with(job, fn):
    fn(job)
    return job


def _res(job):
    return job.task_groups[0].tasks[0].resources


_JOBS = {
    "constraints": lambda: _job(constraints=[
        Constraint(ltarget="${attr.kernel.name}", rtarget="linux",
                   operand="="),
        Constraint(ltarget="${meta.cell}", rtarget="c3", operand="=")]),
    "affinities": lambda: _job(affinities=[
        Affinity(ltarget="${node.datacenter}", rtarget="dc2", operand="=",
                 weight=50),
        Affinity(ltarget="${meta.rack}", rtarget="r1", operand="!=",
                 weight=-20)]),
    "spread_targets": lambda: _job(spreads=[Spread(
        attribute="${node.datacenter}", weight=70,
        spread_target=[SpreadTarget(value="dc1", percent=60),
                       SpreadTarget(value="dc2", percent=40)])]),
    "distinct_hosts": lambda: _job(
        constraints=[Constraint(operand="distinct_hosts")]),
    "distinct_property": lambda: _job(constraints=[Constraint(
        ltarget="${meta.cell}", rtarget="2",
        operand="distinct_property")]),
    "gpu_devices": lambda: _with(_job(), lambda j: setattr(
        _res(j), "devices", [RequestedDevice(name="nvidia/gpu",
                                             count=2)])),
    "system_priority": lambda: _job(type="system", priority=90),
    "bytes_field": lambda: _with(_job(), lambda j: setattr(
        j, "payload", b"\x00\x01bin\xff")),
    "user_map_b": lambda: _with(_job(), lambda j: setattr(
        j, "meta", {"__b": "literal", "ok": "1"})),
    "user_map_bmap": lambda: _with(_job(), lambda j: setattr(
        j, "meta", {"__bmap": "literal"})),
    "user_map_t": lambda: _with(_job(), lambda j: setattr(
        j, "meta", {"__t": "SpreadTarget", "value": "dc9",
                    "percent": 5})),
    "bmap_in_bmap": lambda: _with(_job(), lambda j: setattr(
        j, "meta", {"__bmap": {"__bmap": {"__b": "aGk="}}, "k": "v"})),
    "bmap_in_bmap_literal": lambda: _with(_job(), lambda j: setattr(
        j, "meta", {"__bmap": {"__bmap": {"__b": "z"}}, "k": "v"})),
    "bytes_in_user_map": lambda: _with(_job(), lambda j: setattr(
        j, "meta", {"__b": b"\x01\x02", "raw": b""})),
    "lists_of_structs": lambda: _with(_job(), lambda j: (
        j.task_groups.append(dataclasses.replace(
            j.task_groups[0], name="api", count=3,
            ephemeral_disk=EphemeralDisk(size_mb=500))),
        j.task_groups[0].tasks.append(dataclasses.replace(
            j.task_groups[0].tasks[0], name="sidecar",
            resources=Resources(cpu=100, memory_mb=64))))),
}


def _unknown_fields() -> bytes:
    tree = to_json_tree({"job": to_wire(_job())})
    tree["job"]["no_such_field"] = {"__t": "Constraint", "operand": "="}
    tree["job"]["task_groups"][0]["later_field"] = [1, {"x": 2}]
    tree["sibling"] = {"kept": True}
    return json.dumps(tree).encode()


#: bodies written by hand: marker shapes the SDK never writes, each a
#: wrapper the object hook reaches only after what it wraps
_RAW = {
    "unknown_fields": _unknown_fields,
    "bmap_of_bytes_marker": lambda: b'{"m": {"__bmap": {"__b": "aGk="}}}',
    "bmap_of_bmap_marker":
        lambda: b'{"m": {"__bmap": {"__bmap": {"__b": "aGk="}}}}',
    "bmap_of_bmap_of_bmap":
        lambda: b'{"m": {"__bmap": {"__bmap": {"__bmap": {"a": 1}}}}}',
    "bmap_of_struct":
        lambda: b'{"m": {"__bmap": {"__t": "SpreadTarget", "value": "x"}}}',
    "bytes_then_bmap":
        lambda: b'{"a": {"__b": ""}, "m": {"__bmap": {"b": [{"__b": ""}]}}}',
    "top_level_bmap": lambda: b'{"__bmap": {"__b": "aGk=", "job": 1}}',
    "top_level_bytes": lambda: b'{"__b": "aGk="}',
    "null_tag": lambda: b'{"__t": null, "x": [{"__t": null}]}',
    "list_body": lambda: b'[{"__t": "SpreadTarget", "value": "a"}, 3]',
    "empty_object": lambda: b"{}",
    # built by the hook, never by the three steps (`_FALLS_BACK`)
    "wrapped_bad_bytes": lambda: b'{"m": {"__bmap": {"__b": "!"}}}',
    "wrapped_bmap_of_scalar": lambda: b'{"m": {"__bmap": {"__bmap": 5}}}',
    "dropped_unknown_type": lambda: (
        b'{"job": {"__t": "SpreadTarget", "value": "a",'
        b' "gone": [{"__t": "Nope", "x": {"__b": "!"}}]}}'),
    "dropped_built_struct_holding_failure": lambda: (
        b'{"__t": "SpreadTarget", "gone": {"__t": "SpreadTarget",'
        b' "value": {"__t": "Nope"}}}'),
}

_CASES = {**{k: (lambda f=f: _body(f())) for k, f in _JOBS.items()},
          **_RAW}

#: bodies the one pass refuses and the three steps decode: a wrapped map
#: shaped as a marker that is none (`{"__b": "z"}`, `{"__bmap": 1}`); an
#: unknown type under a field `from_wire` drops
_FALLS_BACK = {"user_map_bmap", "bmap_in_bmap_literal",
               "wrapped_bmap_of_scalar", "dropped_unknown_type",
               "dropped_built_struct_holding_failure"}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_one_pass_equals_three_steps(case):
    raw = _CASES[case]()
    want = _three_steps(raw)
    if case in _FALLS_BACK:
        with pytest.raises(Exception):
            from_json(raw)
        return
    got = from_json(raw)
    assert _typed(got) == _typed(want)
    if case in _JOBS:
        assert isinstance(got["job"], Job)


@pytest.mark.parametrize("raw", [b"", b"{", b'{"job": }', b"nul"],
                         ids=["empty", "open", "no_value", "bad_literal"])
def test_malformed_body_fails_as_the_three_steps_do(raw):
    with pytest.raises(json.JSONDecodeError) as want:
        _three_steps(raw)
    with pytest.raises(json.JSONDecodeError) as got:
        from_json(raw)
    assert str(got.value) == str(want.value)


@pytest.mark.parametrize("raw", [
    b'{"a": {"__b": "z"}}', b'{"a": [{"__t": "Nope"}]}',
    b'{"a": {"__bmap": 5}}',
    b'{"__t": "SpreadTarget", "value": {"__t": "Nope"}}',
    b'{"m": {"__bmap": {"__bmap": {"__b": "z"}}}}',
], ids=["bad_bytes", "unknown_type", "bmap_of_scalar", "in_a_kept_field",
        "unwrapped_bad_bytes"])
def test_what_the_three_steps_refuse_is_refused(raw):
    with pytest.raises(Exception):
        _three_steps(raw)
    with pytest.raises(Exception):
        from_json(raw)


# ---- through HTTPApi ----

@pytest.fixture(scope="module")
def server_agent(tmp_path_factory):
    a = Agent(AgentConfig(
        client=False, heartbeat_ttl=60.0,
        data_dir=str(tmp_path_factory.mktemp("edge") / "data")))
    a.start()
    yield a
    a.shutdown()


def _exchange(addr, method: str, path: str, body: bytes) -> bytes:
    """One request on a fresh connection; the reply's raw bytes."""
    with socket.create_connection(addr, timeout=15) as s:
        s.sendall(f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
                  "Content-Type: application/json\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        buf = b""
        while b"\r\n\r\n" not in buf:
            buf += s.recv(65536)
        head = buf.split(b"\r\n\r\n", 1)[0]
        length = int(next(ln.split(b":", 1)[1] for ln in head.split(b"\r\n")
                          if ln.lower().startswith(b"content-length:")))
        while len(buf) < len(head) + 4 + length:
            buf += s.recv(65536)
        return buf


def _parent_reply(handler_cls, code: int, payload) -> bytes:
    """The bytes the generic writer sends for `payload`: the way every
    reply was written before it took one buffer."""
    h = handler_cls.__new__(handler_cls)
    h.request_version = "HTTP/1.1"
    h.requestline = "PUT / HTTP/1.1"
    h.wfile = io.BytesIO()
    body = json.dumps(to_json_tree(payload)).encode()
    h.send_response(code)
    h.send_header("Content-Type", "application/json")
    h.send_header("Content-Length", str(len(body)))
    h.end_headers()
    h.wfile.write(body)
    return h.wfile.getvalue()


def _undated(reply: bytes) -> bytes:
    return b"\r\n".join(ln for ln in reply.split(b"\r\n")
                        if not ln.startswith(b"Date: "))


class _Seen:
    """What `route` was handed and returned, and the job `job_register`
    was handed, on the agent's own objects."""

    def __init__(self, monkeypatch, agent):
        self.routed, self.registered = [], []
        api, server = agent.http, agent.server
        route, register = api.route, server.job_register

        def seen_route(method, path, query, body, **kw):
            entry = {"body": body, "out": None, "error": None}
            self.routed.append(entry)
            try:
                entry["out"] = route(method, path, query, body, **kw)
            except Exception as e:  # noqa: BLE001 — recorded, re-raised
                entry["error"] = e
                raise
            return entry["out"]

        def seen_register(job, *a, **kw):
            self.registered.append(from_wire(to_wire(job)))
            return register(job, *a, **kw)

        monkeypatch.setattr(api, "route", seen_route)
        monkeypatch.setattr(server, "job_register", seen_register)


def _counts():
    reg = default_registry()
    return tuple(reg.counter(n).value for n in (
        "http.decodes", "http.decode_ms", "http.decode_fallbacks"))


@pytest.mark.parametrize("method,path", [
    ("PUT", "/v1/jobs"), ("POST", "/v1/jobs"),
    ("PUT", "/v1/job/edge-codec"), ("POST", "/v1/job/edge-codec/")])
@pytest.mark.parametrize("kind", ["gpu_devices", "user_map_t",
                                  "bmap_in_bmap", "bmap_in_bmap_literal"])
def test_register_reply_and_job_match_the_parent_path(
        server_agent, monkeypatch, method, path, kind):
    """One decode counted per register, by the one pass or, for a body it
    refuses, by the three steps; the job handed to `job_register` and the
    reply's bytes, Date aside, are the generic path's."""
    seen = _Seen(monkeypatch, server_agent)
    raw = _body(_JOBS[kind]())
    one_pass = kind not in _FALLS_BACK
    n0, ms0, fb0 = _counts()
    reply = _exchange(server_agent.http_addr, method, path, raw)
    n1, ms1, fb1 = _counts()
    assert (n1 - n0, fb1 - fb0) == ((1, 0) if one_pass else (0, 1))
    assert ms1 >= ms0
    [entry] = seen.routed
    tree = from_json_tree(json.loads(raw))
    if one_pass:
        assert isinstance(entry["body"], JobBody)
        assert entry["body"].tree() == tree
    else:
        assert entry["body"] == tree
    [job] = seen.registered
    assert _typed(job) == _typed(_three_steps(raw)["job"])
    assert reply.startswith(b"HTTP/1.1 200 OK\r\n")
    assert b"\r\nDate: " in reply
    want = _parent_reply(server_agent.http.httpd.RequestHandlerClass, 200,
                         entry["out"])
    assert _undated(reply) == _undated(want)


@pytest.mark.parametrize("method,path,raw", [
    ("PUT", "/v1/jobs", b'{"job": '),
    ("PUT", "/v1/jobs", b'{"__t": "Nope"}'),
    ("PUT", "/v1/jobs", b'{"job": {"__t": "Job", "id": "x"},'
                        b' "other": {"__t": "Nope"}}'),
    ("PUT", "/v1/job/edge-codec/plan", b'{"job": {"__t": "Nope"}}'),
    ("PUT", "/v1/no-such-route", b"{}"),
    ("GET", "/v1/jobs", b""),
], ids=["malformed", "unknown_type", "unread_unknown_type", "plan_route",
        "no_route", "get"])
def test_other_bodies_keep_the_generic_path(server_agent, monkeypatch,
                                            method, path, raw):
    """Malformed JSON answers the same 500; a body the one pass cannot
    build falls back to the three steps, which fail (or not) where they
    always have; other routes get the plain tree."""
    seen = _Seen(monkeypatch, server_agent)
    n0, _, fb0 = _counts()
    reply = _exchange(server_agent.http_addr, method, path, raw)
    n1, _, fb1 = _counts()
    assert n1 == n0
    register = method == "PUT" and path == "/v1/jobs"
    assert fb1 - fb0 == (1 if register else 0)
    if raw.endswith(b": "):
        with pytest.raises(json.JSONDecodeError) as e:
            json.loads(raw)
        code, payload = 500, {"error": f"JSONDecodeError: {e.value}"}
        assert not seen.routed
    else:
        [entry] = seen.routed
        tree = from_json_tree(json.loads(raw)) if raw else None
        assert not isinstance(entry["body"], JobBody)
        assert entry["body"] == tree
        err = entry["error"]
        if err is None:
            code, payload = 200, entry["out"]
        elif isinstance(err, HttpError):
            code, payload = err.code, {"error": str(err)}
        else:
            code, payload = 500, {"error": f"{type(err).__name__}: {err}"}
    want = _parent_reply(server_agent.http.httpd.RequestHandlerClass, code,
                         payload)
    assert _undated(reply) == _undated(want)


def test_forwarded_register_carries_the_plain_tree(server_agent,
                                                   monkeypatch):
    """A register for another region is proxied with the body every
    other route gets, rebuilt from the bytes."""
    api = server_agent.http
    sent = []
    monkeypatch.setattr(api, "_forward_region",
                        lambda *a: sent.append(a) or {"eval_id": ""})
    raw = _body(_JOBS["bytes_field"]())
    body = JobBody(from_json(raw), raw)
    api.route("PUT", "/v1/jobs", {"region": "elsewhere"}, body)
    [(region, method, path, query, tree, token)] = sent
    assert (region, method, path) == ("elsewhere", "PUT", "/v1/jobs")
    assert tree == from_json_tree(json.loads(raw))
