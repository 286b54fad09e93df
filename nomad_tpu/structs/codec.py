"""Wire codec for the data-model structs.

Behavioral reference: the reference serializes `nomad/structs` with
msgpack codecs shared by the RPC fabric and the Raft log
(`helper/pool/pool.go:23-28` codec handles, `nomad/fsm.go:180` decode per
message type). Here every dataclass in `nomad_tpu.structs` self-registers
into a codec registry; `to_wire`/`from_wire` produce msgpack-ready trees
tagged with `__t` type markers so nested structs (Job inside Allocation,
DrainStrategy inside Node, ...) round-trip without per-type code.

Consumers: the WAL/FSM (server/fsm.py), the Raft transport, and the
msgpack-RPC fabric; the HTTP API (agent/http.py) through the JSON bridge,
`from_json` for a job register's body.
"""
from __future__ import annotations

import base64
import dataclasses
import importlib
import json
import pkgutil
import threading
from typing import Any, Dict, FrozenSet, Tuple, Type

_TYPE_TAG = "__t"
_REGISTRY: Dict[str, Type] = {}
_FIELDS: Dict[str, Tuple[Type, FrozenSet[str]]] = {}
_REGISTRY_LOCK = threading.Lock()


def _build_registry() -> Dict[str, Type]:
    import nomad_tpu.structs as pkg

    reg: Dict[str, Type] = {}
    for info in pkgutil.iter_modules(pkg.__path__):
        mod = importlib.import_module(f"nomad_tpu.structs.{info.name}")
        for name in dir(mod):
            obj = getattr(mod, name)
            if (isinstance(obj, type) and dataclasses.is_dataclass(obj)
                    and obj.__module__ == mod.__name__):
                existing = reg.get(obj.__name__)
                if existing is not None and existing is not obj:
                    raise RuntimeError(
                        f"duplicate struct name {obj.__name__} in registry"
                    )
                reg[obj.__name__] = obj
    # Wire-visible dataclasses living outside nomad_tpu.structs
    from nomad_tpu.acl.policy import HostVolumeRule, NamespaceRule, Policy
    from nomad_tpu.acl.tokens import ACLPolicy, ACLToken
    from nomad_tpu.scheduler.util import SchedulerConfiguration

    for cls in (SchedulerConfiguration, ACLPolicy, ACLToken, Policy,
                NamespaceRule, HostVolumeRule):
        reg[cls.__name__] = cls
    return reg


def registry() -> Dict[str, Type]:
    """Built once, under a lock, and published whole: the HTTP layer
    decodes on one thread per request, and a reader that saw the
    half-filled dict of a concurrent first build failed with
    "unknown struct type 'Job'" (a burst of first submits does that)."""
    if not _REGISTRY:
        with _REGISTRY_LOCK:
            if not _REGISTRY:
                reg = _build_registry()
                _FIELDS.update(
                    (name, (cls, frozenset(
                        f.name for f in dataclasses.fields(cls))))
                    for name, cls in reg.items())
                _REGISTRY.update(reg)
    return _REGISTRY


def _struct_table() -> Dict[str, Tuple[Type, FrozenSet[str]]]:
    """Type tag → (class, its field names), built once with the registry:
    a decode looks a node's fields up here instead of calling
    `dataclasses.fields` for every struct it builds."""
    if not _REGISTRY:
        registry()
    return _FIELDS


def to_wire(obj: Any) -> Any:
    """Struct tree → msgpack-ready tree (dicts/lists/scalars only)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        out = {_TYPE_TAG: type(obj).__name__}
        for f in dataclasses.fields(obj):
            out[f.name] = to_wire(getattr(obj, f.name))
        return out
    if isinstance(obj, dict):
        return {k: to_wire(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [to_wire(v) for v in obj]
    if isinstance(obj, (str, int, float, bool, bytes)) or obj is None:
        return obj
    raise TypeError(f"unencodable type {type(obj).__name__}: {obj!r}")


def to_json_tree(tree: Any) -> Any:
    """Wire tree → JSON-safe tree (bytes become {"__b": base64}). The
    msgpack transports carry bytes natively; HTTP/JSON needs this bridge.
    Injective: user dicts that collide with the markers are wrapped in
    {"__bmap": ...} so decoding never misreads them."""
    if isinstance(tree, bytes):
        return {"__b": base64.b64encode(tree).decode()}
    if isinstance(tree, dict):
        enc = {k: to_json_tree(v) for k, v in tree.items()}
        if set(tree) & {"__b", "__bmap"}:
            return {"__bmap": enc}
        return enc
    if isinstance(tree, (list, tuple)):
        return [to_json_tree(v) for v in tree]
    return tree


def from_json_tree(tree: Any) -> Any:
    if isinstance(tree, dict):
        if set(tree) == {"__b"}:
            return base64.b64decode(tree["__b"])
        if set(tree) == {"__bmap"}:
            return {k: from_json_tree(v) for k, v in tree["__bmap"].items()}
        return {k: from_json_tree(v) for k, v in tree.items()}
    if isinstance(tree, list):
        return [from_json_tree(v) for v in tree]
    return tree


def from_wire(tree: Any) -> Any:
    """Inverse of to_wire. Unknown fields are ignored (forward compat)."""
    if isinstance(tree, dict):
        tag = tree.get(_TYPE_TAG)
        if tag is not None:
            entry = _struct_table().get(tag)
            if entry is None:
                raise KeyError(f"unknown struct type {tag!r}")
            cls, names = entry
            return cls(**{k: from_wire(v) for k, v in tree.items()
                          if k in names})
        return {k: from_wire(v) for k, v in tree.items()}
    if isinstance(tree, list):
        return [from_wire(v) for v in tree]
    return tree


_NO_MARKER = object()


def from_json(raw: Any) -> Any:
    """`from_wire(from_json_tree(json.loads(raw)))` in one pass: the
    parser's object hook builds each struct, bytes value and map as its
    closing brace is read. Equal to the three steps wherever it returns.
    It raises wherever they raise, and also where they may not, since it
    decodes each node before it knows what holds it: a node under a field
    `from_wire` drops, and a wrapped map shaped as a marker that is none
    (`{"__b": "z"}`, `{"__bmap": 1}`). A caller runs the three steps
    where it raises.

    The hook runs bottom-up, so a `{"__bmap": X}` wrapper reaches it after
    X. An X whose own shape is a marker (`{"__b": s}` or `{"__bmap": Y}`)
    was decoded as one; the wrapper takes the node back, as the plain map
    it stands for. That X is the newest marker decoded, since its closing
    brace is the last one before the wrapper's."""
    table = _struct_table()
    last = [_NO_MARKER, None]  # the newest marker: what it gave, itself

    def hook(node: dict) -> Any:
        tag = node.get(_TYPE_TAG)
        if tag is not None:
            entry = table.get(tag)
            if entry is None:
                raise KeyError(f"unknown struct type {tag!r}")
            cls, names = entry
            del node[_TYPE_TAG]
            if names.issuperset(node):
                return cls(**node)
            return cls(**{k: v for k, v in node.items() if k in names})
        if len(node) != 1:
            return node
        if "__b" in node:
            out = base64.b64decode(node["__b"])
        elif "__bmap" in node:
            out = node["__bmap"]
            if out is last[0]:
                out = last[1]
            elif not (isinstance(out, dict)
                      or dataclasses.is_dataclass(out)):
                raise TypeError(f"__bmap of {type(out).__name__}")
        else:
            return node
        last[0], last[1] = out, node
        return out

    return json.loads(raw, object_hook=hook)
