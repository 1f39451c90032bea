"""Round-5 auth-surface backends: MongoDB (OP_MSG wire), LDAP (BER
simple bind), the TLS-PSK identity store, and the env-override + boot
config check plumbing."""

import asyncio
import struct

import pytest

from emqx_tpu.access import (ALLOW, AccessControl, ClientInfo, DENY,
                             IGNORE, PUBLISH)
from emqx_tpu.auth_db import hash_password
from emqx_tpu.auth_ldap import (LdapAuthenticator, bind_request,
                                parse_bind_response)
from emqx_tpu.auth_mongo import (MongoAuthenticator, MongoAuthorizer,
                                 MongoConnector, bson_decode,
                                 bson_encode)
from emqx_tpu.config import (BrokerConfig, apply_env_overrides,
                             check_config)
from emqx_tpu.psk import PskStore


def run(coro):
    return asyncio.run(coro)


# ------------------------------------------------------------- mongodb

def test_bson_roundtrip():
    doc = {
        "find": "users", "limit": 1, "big": 1 << 40,
        "ok": 1.0, "flag": True, "none": None,
        "filter": {"username": "alice"},
        "arr": ["a", 2, {"x": False}],
    }
    enc = bson_encode(doc)
    dec, off = bson_decode(enc)
    assert off == len(enc)
    assert dec == doc


class FakeMongo:
    """OP_MSG server with a user and an acl collection."""

    def __init__(self):
        self.users = {}
        self.acl = {}
        self.port = 0
        self.server = None

    async def start(self):
        self.server = await asyncio.start_server(
            self._conn, "127.0.0.1", 0
        )
        self.port = self.server.sockets[0].getsockname()[1]

    async def stop(self):
        self.server.close()
        await self.server.wait_closed()

    async def _conn(self, r, w):
        try:
            while True:
                hdr = await r.readexactly(16)
                length, rid, _rto, opcode = struct.unpack("<iiii", hdr)
                payload = await r.readexactly(length - 16)
                doc, _ = bson_decode(payload, 5)
                coll = doc.get("find", "")
                uname = doc.get("filter", {}).get("username", "")
                if coll == "mqtt_user":
                    batch = (
                        [self.users[uname]] if uname in self.users
                        else []
                    )
                else:
                    batch = list(self.acl.get(uname, []))
                reply = bson_encode({
                    "cursor": {"firstBatch": batch, "id": 0,
                               "ns": f"mqtt.{coll}"},
                    "ok": 1.0,
                })
                body = struct.pack("<I", 0) + b"\x00" + reply
                w.write(struct.pack(
                    "<iiii", 16 + len(body), 99, rid, 2013
                ) + body)
                await w.drain()
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            w.close()


def test_mongo_authn_and_acl_prefetch():
    async def t():
        fm = FakeMongo()
        fm.users["alice"] = {
            "username": "alice",
            "password_hash": hash_password("s3cret", "sha256", "na"),
            "salt": "na",
            "is_superuser": False,
        }
        fm.acl["bob"] = [
            {"username": "bob", "permission": "allow",
             "action": "publish", "topics": ["ok/#"]},
            {"username": "bob", "permission": "deny",
             "action": "all", "topic": "#"},
        ]
        await fm.start()
        conn = MongoConnector("127.0.0.1", fm.port)
        authn = MongoAuthenticator(conn)

        d, _ = await authn.authenticate_async(
            ClientInfo(clientid="a", username="alice",
                       password=b"s3cret"))
        assert d == ALLOW
        d, _ = await authn.authenticate_async(
            ClientInfo(clientid="a", username="alice",
                       password=b"wrong"))
        assert d == DENY
        d, _ = await authn.authenticate_async(
            ClientInfo(clientid="a", username="nobody",
                       password=b"x"))
        assert d == IGNORE

        # authorizer through the access layer's prefetch cache
        ac = AccessControl(authz_default="deny")
        ac.db_authz_sources.append(MongoAuthorizer(conn))
        bob = ClientInfo(clientid="b", username="bob")
        await ac.prefetch_acl(bob)
        assert ac.authorize(bob, PUBLISH, "ok/topic")
        assert not ac.authorize(bob, PUBLISH, "other/topic")

        await conn.close()
        await fm.stop()

    run(t())


def test_mongo_commands_pipeline_on_one_connection():
    """PR 3 burn-down: commands no longer serialize on a lock held
    across the round-trip.  The server here collects TWO complete
    OP_MSG requests before answering either (impossible under the old
    lock) and answers in REVERSE order — replies must demultiplex by
    ``responseTo``, each caller seeing its own echoed document."""

    async def t():
        conns = []

        async def handler(r, w):
            conns.append(w)
            seen = []
            for _ in range(2):
                hdr = await r.readexactly(16)
                length, rid, _rto, _op = struct.unpack("<iiii", hdr)
                payload = await r.readexactly(length - 16)
                doc, _ = bson_decode(payload, 5)
                seen.append((rid, doc))
            for rid, doc in reversed(seen):
                reply = bson_encode({
                    "echo": doc.get("find", ""), "ok": 1.0,
                })
                body = struct.pack("<I", 0) + b"\x00" + reply
                w.write(struct.pack(
                    "<iiii", 16 + len(body), 99, rid, 2013
                ) + body)
            await w.drain()

        server = await asyncio.start_server(handler, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        conn = MongoConnector("127.0.0.1", port)
        r1, r2 = await asyncio.wait_for(
            asyncio.gather(
                conn.command({"find": "alpha"}),
                conn.command({"find": "beta"}),
            ),
            5.0,
        )
        assert r1["echo"] == "alpha" and r2["echo"] == "beta"
        assert len(conns) == 1  # both rode one pipelined connection
        await conn.close()
        # Python 3.12's wait_closed() waits for every accepted
        # connection: close the server's half, or it never returns
        for w in conns:
            w.close()
        server.close()
        await server.wait_closed()

    run(t())


# ---------------------------------------------------------------- ldap

def test_ber_bind_codec():
    req = bind_request(7, "uid=alice,dc=x", b"pw")
    assert req[0] == 0x30
    # craft a success BindResponse and parse it
    resp = bytes([0x30, 0x0C, 0x02, 0x01, 7, 0x61, 0x07,
                  0x0A, 0x01, 0x00, 0x04, 0x00, 0x04, 0x00])
    mid, code = parse_bind_response(resp)
    assert (mid, code) == (7, 0)


class FakeLdap:
    def __init__(self, accept):
        self.accept = accept  # dn -> password accepted
        self.port = 0
        self.server = None
        self.seen = []

    async def start(self):
        self.server = await asyncio.start_server(
            self._conn, "127.0.0.1", 0
        )
        self.port = self.server.sockets[0].getsockname()[1]

    async def stop(self):
        self.server.close()
        await self.server.wait_closed()

    async def _conn(self, r, w):
        try:
            data = await r.read(4096)
            # crude parse: find the DN (first 0x04 string) + password
            # ([0] context tag 0x80) inside the BindRequest
            i = data.index(0x60)
            j = data.index(0x04, i)
            dln = data[j + 1]
            dn = data[j + 2:j + 2 + dln].decode()
            k = data.index(0x80, j + 2 + dln)
            pln = data[k + 1]
            pw = data[k + 2:k + 2 + pln]
            self.seen.append((dn, pw))
            code = 0 if self.accept.get(dn) == pw else 49
            mid = data[4]  # messageID (single byte ids in tests)
            w.write(bytes([
                0x30, 0x0C, 0x02, 0x01, mid, 0x61, 0x07,
                0x0A, 0x01, code, 0x04, 0x00, 0x04, 0x00,
            ]))
            await w.drain()
        except Exception:
            pass
        finally:
            w.close()


def test_ldap_bind_auth():
    async def t():
        fl = FakeLdap({
            "uid=alice,ou=users,dc=example,dc=com": b"pw1",
        })
        await fl.start()
        ld = LdapAuthenticator("127.0.0.1", fl.port)
        d, _ = await ld.authenticate_async(
            ClientInfo(clientid="c", username="alice", password=b"pw1"))
        assert d == ALLOW
        d, _ = await ld.authenticate_async(
            ClientInfo(clientid="c", username="alice", password=b"no"))
        assert d == DENY
        # full chain: access control consumes the async provider
        ac = AccessControl(allow_anonymous=False)
        ac.authenticators.append(ld)
        assert ac.has_async_authn
        ok, _ = await ac.authenticate_async(
            ClientInfo(clientid="c", username="alice", password=b"pw1"))
        assert ok
        await fl.stop()

    run(t())


def test_ldap_dn_metacharacters_are_escaped():
    """RFC 4514 escaping closes the authorization-scope bypass: a
    username like 'x,ou=admins,...' must reach the directory as DATA
    inside uid=..., never as extra RDNs rewriting the bind DN."""
    from emqx_tpu.auth_ldap import escape_dn_value

    assert escape_dn_value("alice") == "alice"
    assert escape_dn_value("x,ou=admins") == "x\\,ou\\=admins"
    assert escape_dn_value("#lead ") == "\\#lead\\ "
    assert escape_dn_value(" a+b<c>d;e\"f\\g") == \
        "\\ a\\+b\\<c\\>d\\;e\\\"f\\\\g"
    assert escape_dn_value("n\x00ul") == "n\\00ul"

    async def t():
        evil = "bob,ou=admins,dc=example,dc=com"
        fl = FakeLdap({
            # the directory would accept the ADMIN entry's password:
            # reachable only if the DN arrives unescaped
            "uid=bob,ou=admins,dc=example,dc=com": b"adminpw",
        })
        await fl.start()
        ld = LdapAuthenticator("127.0.0.1", fl.port)
        d, _ = await ld.authenticate_async(ClientInfo(
            clientid="c", username=evil, password=b"adminpw",
        ))
        assert d == DENY  # the escaped DN does not match the admin DN
        seen_dn = fl.seen[0][0]
        assert seen_dn.startswith("uid=bob\\,ou\\=admins")
        assert seen_dn.endswith(",ou=users,dc=example,dc=com")
        await fl.stop()

    run(t())


# ----------------------------------------------------------------- psk

def test_psk_store_file_and_lookup(tmp_path):
    f = tmp_path / "psk.txt"
    f.write_text(
        "# fleet keys\n"
        "dev-1:6162636431323334\n"
        "dev-2:feedface\n"
        "badline\n"
        "dev-3:nothex\n"
    )
    store = PskStore(str(f))
    assert len(store) == 2
    assert store.lookup("dev-1") == b"abcd1234"
    assert store.lookup("dev-2") == bytes.fromhex("feedface")
    assert store.lookup("ghost") is None
    assert store.server_callback(None, b"dev-1") == b"abcd1234"
    assert store.server_callback(None, b"ghost") == b""
    store.insert("dev-9", b"k")
    f.write_text("dev-1:00ff\n")
    assert store.refresh() == 1  # reload replaces the table
    assert store.lookup("dev-9") is None

    import ssl

    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    # on 3.12 this reports the missing hookup instead of crashing
    attached = store.attach(ctx)
    assert attached == hasattr(ctx, "set_psk_server_callback")


# -------------------------------------------- env overrides + check

def test_env_overrides_and_boot_check():
    cfg = BrokerConfig()
    applied = apply_env_overrides(cfg, {
        "EMQX_TPU_MQTT__MAX_INFLIGHT": "64",
        "EMQX_TPU_MQTT__RETAIN_AVAILABLE": "false",
        "EMQX_TPU_DURABLE__LAYOUT": "hash",
        "EMQX_TPU_CLUSTER__ENABLE": "true",
        "UNRELATED": "x",
    })
    assert cfg.mqtt.max_inflight == 64
    assert cfg.mqtt.retain_available is False
    assert cfg.durable.layout == "hash"
    assert cfg.cluster["enable"] is True
    assert len(applied) == 4

    with pytest.raises(ValueError):
        apply_env_overrides(BrokerConfig(),
                            {"EMQX_TPU_MQTT__NO_SUCH_KEY": "1"})

    # EMQX_TPU_NO_DECIDE shares the prefix but is a runtime flag, not
    # a config path: a worker booted with it must not die.  The native
    # libraries' kill switches are gone (PR 28), so their names are
    # unknown paths like any other
    assert apply_env_overrides(
        BrokerConfig(), {"EMQX_TPU_NO_DECIDE": "1"}
    ) == []
    with pytest.raises(ValueError):
        apply_env_overrides(BrokerConfig(),
                            {"EMQX_TPU_NO_" "NATIVE_DISPATCH": "1"})

    assert check_config(BrokerConfig()) == []
    bad = BrokerConfig()
    bad.durable.layout = "bogus"
    bad.listeners[0].type = "quic"  # no certfile
    problems = check_config(bad)
    assert len(problems) == 2


def test_mongo_redials_after_connection_loss():
    """Pump teardown closes the transport, so a later command re-dials
    instead of stalling CONNECT-time auth to its timeout."""

    async def t():
        fm = FakeMongo()
        fm.users["alice"] = {"username": "alice",
                             "password_hash": "x", "salt": ""}
        await fm.start()
        conn = MongoConnector("127.0.0.1", fm.port)
        assert (await conn.find_one(
            "mqtt_user", {"username": "alice"}
        ))["username"] == "alice"
        first_w = conn._w
        first_w.close()  # first: 3.12's wait_closed() waits for it
        await fm.stop()
        await asyncio.sleep(0.05)
        assert conn._w is None  # pump teardown reset the transport
        await fm.start()
        conn.port = fm.port
        row = await asyncio.wait_for(
            conn.find_one("mqtt_user", {"username": "alice"}), 5.0
        )
        assert row["username"] == "alice"
        await conn.close()
        await fm.stop()

    run(t())
