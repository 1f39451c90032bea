"""WS and TLS listeners: full MQTT pub/sub roundtrips over ws:// and
mqtts:// (emqx_listeners.erl:430-447 transport parity)."""

import asyncio
import base64
import datetime
import os

import pytest

from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.broker import ws as W
from emqx_tpu.codec import mqtt as C
from emqx_tpu.config import BrokerConfig, ListenerConfig
from mqtt_client import TestClient


def run(coro):
    return asyncio.run(coro)


class WsTestClient(TestClient):
    """TestClient over a client-side websocket (masked frames)."""

    async def connect(self, **kw):
        r, w = await asyncio.open_connection(self.host, self.port)
        key = base64.b64encode(os.urandom(16)).decode()
        w.write(
            (
                f"GET /mqtt HTTP/1.1\r\nHost: {self.host}\r\n"
                "Upgrade: websocket\r\nConnection: Upgrade\r\n"
                f"Sec-WebSocket-Key: {key}\r\n"
                "Sec-WebSocket-Version: 13\r\n"
                "Sec-WebSocket-Protocol: mqtt\r\n\r\n"
            ).encode()
        )
        await w.drain()
        status = await r.readuntil(b"\r\n\r\n")
        assert b"101" in status.split(b"\r\n")[0], status
        assert b"Sec-WebSocket-Protocol: mqtt" in status

        class _ClientStream(W.WsServerStream):
            def write(self, data: bytes) -> None:  # clients mask
                if data and not self._w.is_closing():
                    self._w.write(
                        W.frame(W.OP_BINARY, data, mask=os.urandom(4))
                    )

        stream = _ClientStream(r, w)
        self.reader = stream
        self.writer = stream
        self._pump = asyncio.get_running_loop().create_task(
            self._read_loop()
        )
        await self.send(
            C.Connect(
                client_id=self.client_id,
                proto_ver=self.version,
                clean_start=kw.get("clean_start", True),
                keepalive=kw.get("keepalive", 60),
                properties=kw.get("properties") or {},
            )
        )
        return await self.expect(C.CONNACK)


def test_ws_pubsub_roundtrip():
    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [
            ListenerConfig(port=0),
            ListenerConfig(name="ws_default", type="ws", port=0),
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        tcp_port, ws_port = (lst.port for lst in srv.listeners)

        sub = WsTestClient(ws_port, "ws-sub")
        ack = await sub.connect()
        assert ack.reason_code == 0
        await sub.subscribe("web/#", qos=1)

        # cross-transport: publish over plain TCP, deliver over WS
        pub = TestClient(tcp_port, "tcp-pub")
        await pub.connect()
        await pub.publish("web/news", b"hello ws", qos=1)
        pkt = await sub.recv_publish()
        assert pkt.topic == "web/news" and pkt.payload == b"hello ws"

        # and WS -> TCP
        await pub.subscribe("from/ws")
        await sub.publish("from/ws", b"reverse", qos=1)
        pkt2 = await pub.recv_publish()
        assert pkt2.payload == b"reverse"

        await pub.disconnect()
        await sub.disconnect()
        await srv.stop()

    run(t())


def test_ws_rejects_plain_http():
    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(name="ws", type="ws", port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        r, w = await asyncio.open_connection(
            "127.0.0.1", srv.listeners[0].port
        )
        w.write(b"GET / HTTP/1.1\r\nHost: x\r\n\r\n")
        await w.drain()
        resp = await r.read(64)
        assert b"400" in resp
        w.close()
        await srv.stop()

    run(t())


def _make_cert(tmp_path):
    """Self-signed localhost certificate via `cryptography`."""
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    from cryptography.x509.oid import NameOID

    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    name = x509.Name(
        [x509.NameAttribute(NameOID.COMMON_NAME, "localhost")]
    )
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (
        x509.CertificateBuilder()
        .subject_name(name)
        .issuer_name(name)
        .public_key(key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(minutes=5))
        .not_valid_after(now + datetime.timedelta(days=1))
        .add_extension(
            x509.SubjectAlternativeName([x509.DNSName("localhost")]),
            critical=False,
        )
        .sign(key, hashes.SHA256())
    )
    certfile = tmp_path / "cert.pem"
    keyfile = tmp_path / "key.pem"
    certfile.write_bytes(cert.public_bytes(serialization.Encoding.PEM))
    keyfile.write_bytes(
        key.private_bytes(
            serialization.Encoding.PEM,
            serialization.PrivateFormat.TraditionalOpenSSL,
            serialization.NoEncryption(),
        )
    )
    return str(certfile), str(keyfile)


def _make_pki(tmp_path):
    """CA + server cert + two client certs + a CRL revoking one
    (`cryptography`-built, no openssl CLI)."""
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import rsa
    from cryptography.x509.oid import NameOID

    now = datetime.datetime.now(datetime.timezone.utc)

    def _name(cn):
        return x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, cn)])

    def _key():
        return rsa.generate_private_key(
            public_exponent=65537, key_size=2048
        )

    def _write(path, pem):
        (tmp_path / path).write_bytes(pem)
        return str(tmp_path / path)

    def _key_pem(key):
        return key.private_bytes(
            serialization.Encoding.PEM,
            serialization.PrivateFormat.TraditionalOpenSSL,
            serialization.NoEncryption(),
        )

    ca_key = _key()
    ca_cert = (
        x509.CertificateBuilder()
        .subject_name(_name("test-ca")).issuer_name(_name("test-ca"))
        .public_key(ca_key.public_key())
        .serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(minutes=5))
        .not_valid_after(now + datetime.timedelta(days=1))
        .add_extension(
            x509.BasicConstraints(ca=True, path_length=None),
            critical=True,
        )
        .sign(ca_key, hashes.SHA256())
    )

    def _issue(cn, san=None):
        key = _key()
        b = (
            x509.CertificateBuilder()
            .subject_name(_name(cn)).issuer_name(_name("test-ca"))
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(minutes=5))
            .not_valid_after(now + datetime.timedelta(days=1))
        )
        if san:
            b = b.add_extension(
                x509.SubjectAlternativeName([x509.DNSName(san)]),
                critical=False,
            )
        return key, b.sign(ca_key, hashes.SHA256())

    srv_key, srv_cert = _issue("localhost", san="localhost")
    good_key, good_cert = _issue("client-good")
    bad_key, bad_cert = _issue("client-revoked")

    crl = (
        x509.CertificateRevocationListBuilder()
        .issuer_name(_name("test-ca"))
        .last_update(now - datetime.timedelta(minutes=5))
        .next_update(now + datetime.timedelta(days=1))
        .add_revoked_certificate(
            x509.RevokedCertificateBuilder()
            .serial_number(bad_cert.serial_number)
            .revocation_date(now - datetime.timedelta(minutes=1))
            .build()
        )
        .sign(ca_key, hashes.SHA256())
    )
    enc = serialization.Encoding.PEM
    return {
        "ca": _write("ca.pem", ca_cert.public_bytes(enc)),
        "ca_key": _write("ca.key", _key_pem(ca_key)),
        "srv_cert": _write("srv.pem", srv_cert.public_bytes(enc)),
        "srv_key": _write("srv.key", _key_pem(srv_key)),
        "good_cert": _write("good.pem", good_cert.public_bytes(enc)),
        "good_key": _write("good.key", _key_pem(good_key)),
        "bad_cert": _write("bad.pem", bad_cert.public_bytes(enc)),
        "bad_key": _write("bad.key", _key_pem(bad_key)),
        "crl": _write("ca.crl", crl.public_bytes(enc)),
    }


async def _mtls_probe(port, ca, certfile, keyfile):
    """True if the broker ACCEPTS this client cert: under TLS 1.3 the
    server's verify verdict arrives AFTER the client handshake
    completes, so acceptance is probed by an MQTT CONNECT->CONNACK
    round trip (a revoked cert gets an alert/EOF instead)."""
    import ssl

    ctx = ssl.create_default_context(cafile=ca)
    ctx.check_hostname = False
    ctx.load_cert_chain(certfile, keyfile)
    try:
        r, w = await asyncio.open_connection(
            "127.0.0.1", port, ssl=ctx, server_hostname="localhost"
        )
    except (ssl.SSLError, ConnectionError):
        return False
    try:
        w.write(C.serialize(C.Connect(client_id="crl-probe")))
        await w.drain()
        data = await asyncio.wait_for(r.read(4), 5.0)
        return len(data) > 0 and data[0] >> 4 == 2  # CONNACK
    except (ssl.SSLError, ConnectionError, asyncio.TimeoutError):
        return False
    finally:
        w.close()


def test_tls_crl_rejects_revoked_client(tmp_path):
    """mTLS listener with a CRL (emqx_crl_cache role): a revoked
    client cert is rejected; an unrevoked one connects."""
    pki = _make_pki(tmp_path)

    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [
            ListenerConfig(
                name="mtls", type="ssl", port=0,
                certfile=pki["srv_cert"], keyfile=pki["srv_key"],
                cacertfile=pki["ca"], verify=True,
                crlfile=pki["crl"],
            )
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        port = srv.listeners[0].port

        assert await _mtls_probe(port, pki["ca"], pki["good_cert"],
                                 pki["good_key"])
        assert not await _mtls_probe(port, pki["ca"], pki["bad_cert"],
                                     pki["bad_key"])
        await srv.stop()

    run(t())


def test_tls_crl_requires_verify(tmp_path):
    """crlfile without verify=true is a misconfiguration (no client
    cert requested -> nothing to revoke-check) and must fail loudly,
    not silently skip revocation."""
    import pytest

    pki = _make_pki(tmp_path)

    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [
            ListenerConfig(
                name="mtls", type="ssl", port=0,
                certfile=pki["srv_cert"], keyfile=pki["srv_key"],
                cacertfile=pki["ca"], crlfile=pki["crl"],
            )
        ]
        srv = BrokerServer(cfg)
        with pytest.raises(ValueError, match="verify"):
            await srv.start()
        await srv.stop()

    run(t())


def test_tls_crl_hot_reload(tmp_path):
    """Revoking a cert by rewriting the CRL file takes effect on new
    handshakes after maybe_reload_crl, without a listener restart."""
    import os

    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization

    pki = _make_pki(tmp_path)

    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [
            ListenerConfig(
                name="mtls", type="ssl", port=0,
                certfile=pki["srv_cert"], keyfile=pki["srv_key"],
                cacertfile=pki["ca"], verify=True,
                crlfile=pki["crl"],
            )
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        lst = srv.listeners[0]
        port = lst.port

        # 'good' connects fine against the original CRL
        assert await _mtls_probe(port, pki["ca"], pki["good_cert"],
                                 pki["good_key"])

        # roll the CRL forward: now 'good' is revoked too
        from cryptography.x509.oid import NameOID

        now = datetime.datetime.now(datetime.timezone.utc)
        ca_name = x509.Name(
            [x509.NameAttribute(NameOID.COMMON_NAME, "test-ca")]
        )
        good = x509.load_pem_x509_certificate(
            open(pki["good_cert"], "rb").read()
        )
        bad = x509.load_pem_x509_certificate(
            open(pki["bad_cert"], "rb").read()
        )
        ca_key = serialization.load_pem_private_key(
            open(pki["ca_key"], "rb").read(), password=None
        )
        builder = (
            x509.CertificateRevocationListBuilder()
            .issuer_name(ca_name)
            .last_update(now)
            .next_update(now + datetime.timedelta(days=1))
        )
        for cert in (good, bad):
            builder = builder.add_revoked_certificate(
                x509.RevokedCertificateBuilder()
                .serial_number(cert.serial_number)
                .revocation_date(now)
                .build()
            )
        crl2 = builder.sign(ca_key, hashes.SHA256())
        with open(pki["crl"], "wb") as f:
            f.write(crl2.public_bytes(serialization.Encoding.PEM))
        os.utime(pki["crl"], (0, 10**10))  # force a new mtime
        assert lst.maybe_reload_crl()

        assert not await _mtls_probe(port, pki["ca"],
                                     pki["good_cert"],
                                     pki["good_key"])
        await srv.stop()

    run(t())


def test_tls_pubsub_roundtrip(tmp_path):
    import ssl

    certfile, keyfile = _make_cert(tmp_path)

    class TlsTestClient(TestClient):
        async def connect(self, **kw):
            ctx = ssl.create_default_context(cafile=certfile)
            ctx.check_hostname = False
            self.reader, self.writer = await asyncio.open_connection(
                self.host, self.port, ssl=ctx, server_hostname="localhost"
            )
            self._pump = asyncio.get_running_loop().create_task(
                self._read_loop()
            )
            await self.send(
                C.Connect(
                    client_id=self.client_id,
                    proto_ver=self.version,
                    clean_start=True,
                    keepalive=60,
                )
            )
            return await self.expect(C.CONNACK)

    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [
            ListenerConfig(
                name="ssl",
                type="ssl",
                port=0,
                certfile=certfile,
                keyfile=keyfile,
            )
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        port = srv.listeners[0].port

        sub = TlsTestClient(port, "tls-sub")
        ack = await sub.connect()
        assert ack.reason_code == 0
        await sub.subscribe("sec/#", qos=1)
        pub = TlsTestClient(port, "tls-pub")
        await pub.connect()
        await pub.publish("sec/data", b"encrypted hi", qos=1)
        pkt = await sub.recv_publish()
        assert pkt.payload == b"encrypted hi"
        await pub.disconnect()
        await sub.disconnect()
        await srv.stop()

    run(t())


# ---- the boot heap, frozen while a server of the process serves ----

def _plain_server():
    cfg = BrokerConfig()
    cfg.listeners = [ListenerConfig(bind="127.0.0.1", port=0)]
    return BrokerServer(cfg)


def test_start_freezes_the_boot_heap_and_the_last_stop_thaws_it():
    import gc

    async def t():
        base = BrokerServer._serving
        a, b = _plain_server(), _plain_server()
        await a.start()
        assert BrokerServer._serving == base + 1
        assert gc.get_freeze_count() > 0
        await b.start()
        await a.stop()
        # another server of the process still serves: frozen it stays
        assert BrokerServer._serving == base + 1
        assert gc.get_freeze_count() > 0
        await a.stop()  # a second stop() of one server counts once
        assert BrokerServer._serving == base + 1
        await b.stop()
        assert BrokerServer._serving == base
        if base == 0:
            assert gc.get_freeze_count() == 0

    run(t())


def test_a_full_collection_walks_what_came_after_start_alone():
    import gc
    import weakref

    class Node:
        pass

    async def t():
        boot = [[i] for i in range(2000)]  # tracked, alive at start()
        srv = _plain_server()
        await srv.start()
        try:
            after = [[i] for i in range(50)]
            seen = {id(o) for o in gc.get_objects()}
            assert not seen.intersection(map(id, boot))
            assert seen.issuperset(map(id, after))
            # and the collector still serves what came after: a cycle
            # that dies while the server runs is collected
            x, y = Node(), Node()
            x.peer, y.peer = y, x
            gone = weakref.ref(x)
            del x, y
            gc.collect()
            assert gone() is None
        finally:
            await srv.stop()
        if BrokerServer._serving == 0:
            assert {id(o) for o in gc.get_objects()}.issuperset(
                map(id, boot)
            )

    run(t())
