"""Resource layer: rule -> buffered HTTP sink with injected failures —
no loss within buffer bounds (emqx_resource_buffer_worker semantics)."""

import asyncio
import json

from aiohttp import web

from emqx_tpu.broker.listener import BrokerServer
from emqx_tpu.config import BrokerConfig, ListenerConfig
from emqx_tpu.resources import CONNECTED, DISCONNECTED, HttpSink, Resource
from emqx_tpu.rules.engine import SinkAction
from mqtt_client import TestClient


def run(coro):
    return asyncio.run(coro)


class FlakyServer:
    """Local HTTP server that fails the first `fail_first` POSTs."""

    def __init__(self, fail_first: int = 0):
        self.fail_first = fail_first
        self.requests = 0
        self.bodies = []
        self.port = None
        self._runner = None

    async def start(self):
        app = web.Application()

        async def handle(request):
            self.requests += 1
            if self.requests <= self.fail_first:
                return web.Response(status=503)
            self.bodies.append(await request.text())
            return web.Response(status=200)

        async def head(request):
            return web.Response(status=200)

        app.router.add_post("/ingest", handle)
        app.router.add_head("/ingest", head)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]

    async def stop(self):
        await self._runner.cleanup()


def test_rule_to_http_sink_with_failures():
    async def t():
        http = FlakyServer(fail_first=3)
        await http.start()

        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        broker = srv.broker
        await broker.resources.create(
            "wh1",
            HttpSink(f"http://127.0.0.1:{http.port}/ingest"),
            retry_base=0.01,
        )
        broker.rules.add_rule(
            "fwd",
            'SELECT payload.v AS v, topic FROM "ing/#" WHERE payload.v > 0',
            actions=[SinkAction(resource_id="wh1")],
        )

        pub = TestClient(srv.listeners[0].port, "p")
        await pub.connect()
        for v in range(1, 6):
            await pub.publish("ing/a", json.dumps({"v": v}).encode(), qos=1)
        await pub.disconnect()

        # the first 3 POSTs fail; retries must deliver ALL 5 in order
        # (the server holds a body before the worker has read its
        # answer and counted the success: wait for both)
        worker = broker.resources.get("wh1")
        for _ in range(200):
            if len(http.bodies) == 5 and worker.stats["success"] == 5:
                break
            await asyncio.sleep(0.02)
        assert [json.loads(b)["v"] for b in http.bodies] == [1, 2, 3, 4, 5]
        assert worker.stats["success"] == 5
        assert worker.stats["retried"] >= 3
        assert worker.stats["dropped"] == 0
        assert worker.status == CONNECTED
        assert broker.resources.info()["wh1"]["buffered"] == 0

        await srv.stop()
        await http.stop()

    run(t())


def test_buffer_bound_drops_oldest():
    class Black(Resource):
        async def on_query(self, q):
            raise RuntimeError("down")

        async def health_check(self):
            return False

    async def t():
        from emqx_tpu.resources import BufferWorker

        w = BufferWorker(Black(), max_buffer=3, retry_base=0.01)
        await w.start()
        for i in range(5):
            w.enqueue(f"q{i}")
        assert len(w) == 3
        assert w.stats["dropped"] == 2
        assert list(w._buf) == ["q2", "q3", "q4"]
        await asyncio.sleep(0.05)
        assert w.status == DISCONNECTED
        await w.stop()

    run(t())


def test_sink_payload_template():
    async def t():
        http = FlakyServer()
        await http.start()
        cfg = BrokerConfig()
        cfg.listeners = [ListenerConfig(port=0)]
        srv = BrokerServer(cfg)
        await srv.start()
        await srv.broker.resources.create(
            "wh2", HttpSink(f"http://127.0.0.1:{http.port}/ingest")
        )
        srv.broker.rules.add_rule(
            "fmt",
            'SELECT payload.name AS name FROM "fmt/#"',
            actions=[
                SinkAction(resource_id="wh2", payload="hello ${name}")
            ],
        )
        pub = TestClient(srv.listeners[0].port, "p2")
        await pub.connect()
        await pub.publish("fmt/x", b'{"name": "ada"}', qos=1)
        await pub.disconnect()
        for _ in range(100):
            if http.bodies:
                break
            await asyncio.sleep(0.02)
        assert http.bodies == ["hello ada"]
        await srv.stop()
        await http.stop()

    run(t())
