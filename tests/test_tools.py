"""Tools-layer tests: argv/identity helpers and the non-interactive
pieces of dhtnode/dhtchat/dhtscanner (the interactive REPL is driven in
CI-style smoke runs, not here)."""

import os

import pytest

from opendht_tpu import crypto
from opendht_tpu.infohash import InfoHash
from opendht_tpu.tools.common import (load_identity, make_arg_parser,
                                      parse_bootstrap, save_identity)
from opendht_tpu.tools.dhtnode import to_hash


def test_parse_bootstrap_forms():
    assert parse_bootstrap("") is None
    assert parse_bootstrap("host") == ("host", 4222)
    assert parse_bootstrap("host:4000") == ("host", 4000)
    assert parse_bootstrap("[2001:db8::1]:4000") == ("2001:db8::1", 4000)
    assert parse_bootstrap("[2001:db8::1]") == ("2001:db8::1", 4222)
    assert parse_bootstrap("2001:db8::1") == ("2001:db8::1", 4222)


def test_to_hash_hex_vs_text():
    h = InfoHash.get("x")
    assert to_hash(h.hex()) == h                 # 40-hex passes through
    assert to_hash("some words") == InfoHash.get("some words")


def test_identity_save_load(tmp_path):
    ident = crypto.generate_identity("tools-test", key_length=1024)
    prefix = str(tmp_path / "id")
    save_identity(ident, prefix)
    assert os.path.exists(prefix + ".pem")
    assert os.path.exists(prefix + ".crt")
    loaded = load_identity(prefix)
    assert loaded is not None
    assert loaded.second.get_id() == ident.second.get_id()
    # loaded key can still sign for the same public key
    sig = loaded.first.sign(b"data")
    assert ident.first.public_key().check_signature(b"data", sig)


def test_state_save_load_roundtrip(tmp_path):
    """Checkpoint/resume: nodes+values exported to a file come back on a
    fresh runner (↔ exportNodes/exportValues persistence, SURVEY §5)."""
    import time
    from opendht_tpu.core.value import Value
    from opendht_tpu.runtime.config import NodeStatus
    from opendht_tpu.runtime.runner import DhtRunner
    from opendht_tpu.tools.common import load_state, save_state

    a, b, c = DhtRunner(), DhtRunner(), None
    try:
        a.run(0)
        b.run(0)
        b.bootstrap("127.0.0.1", a.get_bound_port())
        deadline = time.monotonic() + 20.0
        while (b.get_status() is not NodeStatus.CONNECTED
               and time.monotonic() < deadline):
            time.sleep(0.05)
        key = InfoHash.get("state-key")
        assert b.put_sync(key, Value(b"persisted"), timeout=20.0)
        path = str(tmp_path / "state.mp")
        save_state(b, path)
        b.join()

        c = DhtRunner()
        c.run(0)
        n_nodes, n_keys = load_state(c, path)
        assert n_nodes >= 1 and n_keys >= 1
        vals = c.get_sync(key, timeout=20.0)
        assert any(v.data == b"persisted" for v in vals)
    finally:
        a.join()
        b.join()
        if c is not None:
            c.join()


def test_arg_parser_defaults():
    args = make_arg_parser("t").parse_args([])
    assert args.port == 0 and args.bootstrap == "" and not args.identity
    args = make_arg_parser("t").parse_args(
        ["-p", "4222", "-b", "h:1", "-i", "--proxyserver", "8080"])
    assert (args.port, args.bootstrap, args.identity, args.proxyserver) == \
        (4222, "h:1", True, 8080)


def test_tpu_flag_exits_non_zero_without_a_tpu():
    """``--tpu`` gets the chip or the node does not start — it must
    never come up on the CPU backend unnoticed (the tests run on it)."""
    from opendht_tpu.tools import require_tpu
    from opendht_tpu.tools.common import setup_node
    with pytest.raises(SystemExit) as e:
        require_tpu()
    assert e.value.code not in (0, None)
    args = make_arg_parser("t").parse_args(["--tpu"])
    with pytest.raises(SystemExit):
        setup_node(args)
