"""OCPP-J gateway: charge point over WebSocket bridged to MQTT
topics (emqx_gateway_ocpp parity)."""

import asyncio
import base64
import json
import os

from emqx_tpu.broker import ws as W
from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.config import BrokerConfig, ListenerConfig
from mqtt_client import TestClient


# a limit, not a pace: every wait below ends when its frame arrives.
# A window of the broker takes seconds now and then with six test
# workers on the cores, and the defaults (2 s, 3 s) timed out then
WAIT = 30.0


def run(coro):
    return asyncio.run(coro)


class OcppClient:
    """Raw OCPP-J websocket charge-point client (masked frames)."""

    def __init__(self, port, cpid, proto="ocpp1.6"):
        self.port = port
        self.cpid = cpid
        self.proto = proto

    async def handshake_status(self) -> bytes:
        self.r, self.w = await asyncio.open_connection(
            "127.0.0.1", self.port
        )
        key = base64.b64encode(os.urandom(16)).decode()
        self.w.write((
            f"GET /ocpp/{self.cpid} HTTP/1.1\r\nHost: x\r\n"
            "Upgrade: websocket\r\nConnection: Upgrade\r\n"
            f"Sec-WebSocket-Key: {key}\r\n"
            "Sec-WebSocket-Version: 13\r\n"
            f"Sec-WebSocket-Protocol: {self.proto}\r\n\r\n"
        ).encode())
        await self.w.drain()
        return await self.r.readuntil(b"\r\n\r\n")

    async def connect(self):
        status = await self.handshake_status()
        assert b"101" in status.split(b"\r\n")[0], status
        assert b"Sec-WebSocket-Protocol: ocpp1.6" in status
        return self

    def send(self, arr):
        self.w.write(W.frame(
            0x1, json.dumps(arr).encode(), mask=os.urandom(4)
        ))

    async def recv(self, timeout=WAIT):
        while True:
            opcode, fin, payload = await asyncio.wait_for(
                W.read_frame(self.r), timeout
            )
            if opcode == 0x1:
                return json.loads(payload)

    def close(self):
        self.w.close()


def test_ocpp_call_result_and_downlink():
    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        cfg.gateways = [
            {"type": "ocpp", "bind": "127.0.0.1", "port": 0}
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        gw = srv.broker.gateways.get("ocpp")

        csms = TestClient(srv.listeners[0].port, "csms")
        await csms.connect()
        await csms.subscribe("ocpp/cp/#", qos=1)

        cp = await OcppClient(gw.port, "CP001").connect()

        # -------- upstream CALL -> ocpp/cp/CP001
        cp.send([2, "m1", "BootNotification",
                 {"chargePointModel": "X1", "chargePointVendor": "emq"}])
        pub = await csms.recv_publish(timeout=WAIT)
        assert pub.topic == "ocpp/cp/CP001"
        body = json.loads(pub.payload)
        assert body["type"] == 2 and body["action"] == "BootNotification"
        assert body["payload"]["chargePointModel"] == "X1"

        # -------- downstream CALL: csms -> ocpp/cs/CP001 -> socket
        await csms.publish("ocpp/cs/CP001", json.dumps({
            "type": 2, "id": "srv-1", "action": "RemoteStartTransaction",
            "payload": {"idTag": "ABC"},
        }).encode(), qos=1)
        arr = await cp.recv()
        assert arr == [2, "srv-1", "RemoteStartTransaction",
                       {"idTag": "ABC"}]

        # -------- the charge point's CALLRESULT -> cp/CP001/Reply
        cp.send([3, "srv-1", {"status": "Accepted"}])
        pub = await csms.recv_publish(timeout=WAIT)
        assert pub.topic == "ocpp/cp/CP001/Reply"
        body = json.loads(pub.payload)
        assert body["type"] == 3 and body["payload"]["status"] == \
            "Accepted"

        # -------- CALLERROR goes to the Reply topic too
        cp.send([4, "srv-2", "NotSupported", "nope", {}])
        pub = await csms.recv_publish(timeout=WAIT)
        assert pub.topic == "ocpp/cp/CP001/Reply"
        body = json.loads(pub.payload)
        assert body["type"] == 4 and body["error_code"] == "NotSupported"

        # -------- malformed frame answers a ProtocolError on-socket
        cp.send({"not": "an array"})
        arr = await cp.recv()
        assert arr[0] == 4 and arr[2] == "ProtocolError"

        cp.close()
        await csms.disconnect()
        await srv.stop()

    run(t())


def test_ocpp_rejects_bad_cpid_and_subprotocol():
    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        cfg.gateways = [
            {"type": "ocpp", "bind": "127.0.0.1", "port": 0}
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        gw = srv.broker.gateways.get("ocpp")

        # wildcard-smuggling cpids must not become subscriptions
        for cpid in ("%23", "%2B", "a%2Fb", "+"):
            c = OcppClient(gw.port, cpid)
            status = await c.handshake_status()
            assert b"101" in status.split(b"\r\n")[0]
            # server closes without attaching a session
            op, _, _ = await asyncio.wait_for(
                W.read_frame(c.r), WAIT
            )
            assert op == 0x8  # close frame
            c.close()
        assert srv.broker.cm.lookup("#") is None
        assert srv.broker.cm.lookup("+") is None

        # unsupported subprotocol: upgrade rejected outright
        c = OcppClient(gw.port, "CP009", proto="ocpp2.0.1")
        status = await c.handshake_status()
        assert b"400" in status.split(b"\r\n")[0]
        c.close()
        await srv.stop()

    run(t())


def test_ocpp_downlink_flood_beyond_inflight_window():
    """Deliveries settle on socket handoff: far more than the 32-slot
    inflight window must arrive (a silent stall at 32 was the bug)."""

    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        cfg.gateways = [
            {"type": "ocpp", "bind": "127.0.0.1", "port": 0}
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        gw = srv.broker.gateways.get("ocpp")

        csms = TestClient(srv.listeners[0].port, "csms-f")
        await csms.connect()
        await csms.subscribe("ocpp/cp/#", qos=1)
        cp = await OcppClient(gw.port, "CP077").connect()
        cp.send([2, "m1", "Heartbeat", {}])
        # the heartbeat (cp is attached)
        await csms.recv_publish(timeout=WAIT)

        for i in range(100):
            await csms.publish("ocpp/cs/CP077", json.dumps({
                "type": 2, "id": f"c{i}", "action": "GetConfiguration",
                "payload": {},
            }).encode(), qos=1)
        got = set()
        for _ in range(100):
            arr = await cp.recv()
            got.add(arr[1])
        assert got == {f"c{i}" for i in range(100)}

        cp.close()
        await csms.disconnect()
        await srv.stop()

    run(t())


def test_ocpp_session_registered_and_cleanup():
    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        cfg.gateways = [
            {"type": "ocpp", "bind": "127.0.0.1", "port": 0}
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        gw = srv.broker.gateways.get("ocpp")

        cp = await OcppClient(gw.port, "CP002").connect()
        cp.send([2, "m1", "Heartbeat", {}])
        for _ in range(50):
            if srv.broker.cm.connected("CP002"):
                break
            await asyncio.sleep(0.02)
        assert srv.broker.cm.connected("CP002")
        cp.close()
        for _ in range(100):
            if not srv.broker.cm.connected("CP002"):
                break
            await asyncio.sleep(0.02)
        assert not srv.broker.cm.connected("CP002")
        await srv.stop()

    run(t())


def test_ocpp_schema_validation():
    """OCPP 1.6 core-profile CALL payloads validate against the
    per-action schemas: violations answer CALLERROR
    TypeConstraintViolation on-socket and never reach the broker;
    valid frames and unknown actions pass."""

    async def t():
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        cfg.gateways = [
            {"type": "ocpp", "bind": "127.0.0.1", "port": 0}
        ]
        srv = BrokerServer(cfg)
        await srv.start()
        gw = srv.broker.gateways.get("ocpp")

        csms = TestClient(srv.listeners[0].port, "csms")
        await csms.connect()
        await csms.subscribe("ocpp/cp/#", qos=1)
        cp = await OcppClient(gw.port, "CP9").connect()

        # missing required field
        cp.send([2, "b1", "BootNotification",
                 {"chargePointModel": "X1"}])
        arr = await cp.recv()
        assert arr[0] == 4 and arr[1] == "b1"
        assert arr[2] == "TypeConstraintViolation"

        # wrong type
        cp.send([2, "s1", "StatusNotification",
                 {"connectorId": "one", "errorCode": "NoError",
                  "status": "Available"}])
        arr = await cp.recv()
        assert arr[2] == "TypeConstraintViolation"

        # enum violation
        cp.send([2, "s2", "StatusNotification",
                 {"connectorId": 1, "errorCode": "NoError",
                  "status": "Snoozing"}])
        arr = await cp.recv()
        assert arr[2] == "TypeConstraintViolation"

        # valid frames reach the broker
        cp.send([2, "s3", "StatusNotification",
                 {"connectorId": 1, "errorCode": "NoError",
                  "status": "Charging"}])
        pub = await csms.recv_publish(timeout=WAIT)
        assert json.loads(pub.payload)["payload"]["status"] == \
            "Charging"

        # unknown actions pass through unvalidated (strict=false)
        cp.send([2, "d1", "DataTransfer", {"vendorId": "x",
                                           "weird": [1, 2]}])
        pub = await csms.recv_publish(timeout=WAIT)
        assert json.loads(pub.payload)["action"] == "DataTransfer"

        cp.close()
        await csms.disconnect()
        await srv.stop()

    run(t())
