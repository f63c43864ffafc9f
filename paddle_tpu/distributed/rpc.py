"""RPC framework.

Reference: `python/paddle/distributed/rpc/rpc.py` — init_rpc (brpc
server per worker + master rendezvous), rpc_sync / rpc_async (pickled
python callables executed on the remote worker), get_worker_info,
shutdown.

TPU-native: the transport is the launcher's HTTP KV store (the same
service that backs rendezvous and the eager host collectives) — each
worker runs a daemon thread polling its call queue, executes the
pickled callable, and posts the pickled result.  No brpc build, no
ports per worker, works anywhere the launcher works.
"""
from __future__ import annotations

import base64
import os
import pickle
import threading
import time
import uuid
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Optional

__all__ = ["init_rpc", "rpc_sync", "rpc_async", "get_worker_info",
           "get_all_worker_infos", "get_current_worker_info", "shutdown"]


@dataclass
class WorkerInfo:
    name: str
    rank: int
    ip: str = "127.0.0.1"
    port: int = 0


_state = {"kv": None, "name": None, "rank": None, "world": None,
          "thread": None, "stop": None}


def _enc(obj) -> str:
    try:
        blob = pickle.dumps(obj, protocol=4)
    except (AttributeError, TypeError, pickle.PicklingError):
        # lambdas / closures: fall back to cloudpickle like the
        # reference's serialization of arbitrary python callables
        import cloudpickle
        blob = cloudpickle.dumps(obj)
    return base64.b64encode(blob).decode()


def _dec(s: str):
    return pickle.loads(base64.b64decode(s))


def _serve_loop():
    kv = _state["kv"]
    name = _state["name"]
    prefix = f"rpc/call/{name}"
    while not _state["stop"].is_set():
        try:
            calls = kv.prefix(prefix)
        except Exception:
            time.sleep(0.1)
            continue
        for key, raw in sorted(calls.items()):
            try:
                req = _dec(raw)
            except AttributeError:
                # a function the caller pickled by name is not defined
                # here YET: a peer's call can arrive between init_rpc()
                # and the script's own `def`s.  Left for the next poll;
                # the caller's timeout bounds the wait
                continue
            kv.delete(key)
            try:
                fn = req["fn"]
                out = fn(*req.get("args", ()), **(req.get("kwargs") or {}))
                payload = {"ok": True, "value": out}
            except Exception as e:  # ship the exception back, like brpc
                payload = {"ok": False, "error": e}
            kv.put(f"rpc/ret/{req['rid']}", _enc(payload))
        time.sleep(0.02)


def init_rpc(name: str, rank: Optional[int] = None,
             world_size: Optional[int] = None,
             master_endpoint: Optional[str] = None):
    """Register this worker and start serving (reference rpc.py
    init_rpc; master via PADDLE_KV_MASTER / PADDLE_MASTER_ENDPOINT)."""
    from .launch.master import KVClient
    ep = master_endpoint or os.environ.get("PADDLE_KV_MASTER") \
        or os.environ.get("PADDLE_MASTER_ENDPOINT")
    if ep is None:
        raise ValueError("init_rpc needs master_endpoint or "
                         "PADDLE_KV_MASTER (run under the launcher)")
    rank = rank if rank is not None \
        else int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = world_size if world_size is not None \
        else int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    kv = KVClient(ep if "://" in ep else f"http://{ep}")
    _state.update(kv=kv, name=name, rank=rank, world=world,
                  stop=threading.Event())
    kv.put(f"rpc/workers/{name}", _enc(WorkerInfo(name, rank)))
    t = threading.Thread(target=_serve_loop, daemon=True,
                         name=f"rpc-serve-{name}")
    _state["thread"] = t
    t.start()
    # wait for the full gang to register (reference: barrier in init_rpc)
    kv.wait_n("rpc/workers", world, timeout=60)


def get_worker_info(name: str) -> WorkerInfo:
    raw = _state["kv"].get(f"rpc/workers/{name}")
    if raw is None:
        raise RuntimeError(f"unknown rpc worker {name!r}")
    return _dec(raw)


def get_all_worker_infos():
    got = _state["kv"].prefix("rpc/workers")
    return sorted((_dec(v) for v in got.values()), key=lambda w: w.rank)


def get_current_worker_info() -> WorkerInfo:
    return WorkerInfo(_state["name"], _state["rank"])


def rpc_async(to: str, fn, args=None, kwargs=None,
              timeout: float = 30.0) -> Future:
    """Run fn(*args, **kwargs) on worker `to`; returns a Future."""
    kv = _state["kv"]
    if kv is None:
        raise RuntimeError("call init_rpc first")
    rid = uuid.uuid4().hex
    kv.put(f"rpc/call/{to}/{time.time():020.6f}.{rid}",
           _enc({"rid": rid, "fn": fn, "args": tuple(args or ()),
                 "kwargs": dict(kwargs or {})}))
    fut: Future = Future()

    def waiter():
        deadline = time.time() + timeout
        while time.time() < deadline:
            raw = kv.get(f"rpc/ret/{rid}")
            if raw is not None:
                kv.delete(f"rpc/ret/{rid}")
                payload = _dec(raw)
                if payload["ok"]:
                    fut.set_result(payload["value"])
                else:
                    fut.set_exception(payload["error"])
                return
            time.sleep(0.02)
        fut.set_exception(TimeoutError(
            f"rpc to {to!r} timed out after {timeout}s"))
        # the server may still deliver late: reap the orphaned result so
        # the shared KV store doesn't accumulate pickled payloads
        def _reap():
            time.sleep(max(timeout, 5.0))
            try:
                kv.delete(f"rpc/ret/{rid}")
            except Exception:
                pass
        threading.Thread(target=_reap, daemon=True).start()

    threading.Thread(target=waiter, daemon=True).start()
    return fut


def rpc_sync(to: str, fn, args=None, kwargs=None, timeout: float = 30.0):
    return rpc_async(to, fn, args, kwargs, timeout).result()


def shutdown(graceful: bool = True):
    if _state["stop"] is not None:
        _state["stop"].set()
    if _state["kv"] is not None and _state["name"]:
        try:
            _state["kv"].delete(f"rpc/workers/{_state['name']}")
        except Exception:
            pass
    _state.update(kv=None, name=None, thread=None)
