"""Control-plane RPC tests (framed JSON over TCP; rpc/ package analog)."""

import threading

import pytest

from tony_tpu.cluster.rpc import RpcClient, RpcError, RpcServer


@pytest.fixture()
def server():
    srv = RpcServer(secret="s3cret")
    srv.register("echo", lambda **kw: kw)
    srv.register("boom", lambda: 1 / 0)
    srv.start()
    yield srv
    srv.stop()


def client_for(server, secret="s3cret"):
    host, port = server.address
    return RpcClient(host, port, secret=secret)


class TestRpc:
    def test_echo_roundtrip(self, server):
        c = client_for(server)
        assert c.call("echo", a=1, b=[1, 2], c={"x": "y"}) == {"a": 1, "b": [1, 2], "c": {"x": "y"}}

    def test_remote_exception_surfaces(self, server):
        with pytest.raises(RpcError, match="ZeroDivisionError"):
            client_for(server).call("boom")

    def test_unknown_method(self, server):
        with pytest.raises(RpcError, match="unknown method"):
            client_for(server).call("nope")

    def test_bad_auth_rejected(self, server):
        with pytest.raises(RpcError, match="authentication"):
            client_for(server, secret="wrong").call("echo", a=1)

    def test_many_sequential_calls_one_connection(self, server):
        c = client_for(server)
        for i in range(100):
            assert c.call("echo", i=i) == {"i": i}

    def test_concurrent_clients(self, server):
        errors = []

        def worker(n):
            try:
                c = client_for(server)
                for i in range(20):
                    assert c.call("echo", n=n, i=i) == {"n": n, "i": i}
                c.close()
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(n,)) for n in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors

    def test_reconnect_after_server_side_drop(self, server):
        c = client_for(server)
        assert c.call("echo", a=1) == {"a": 1}
        c._sock.close()  # simulate a dropped connection
        assert c.call("echo", a=2) == {"a": 2}  # transparent reconnect

    @pytest.mark.parametrize("fault", [KeyboardInterrupt, ValueError])
    def test_a_call_cut_before_its_answer_is_read_leaves_none_for_the_next_call(self, server, monkeypatch, fault):
        """`tony serve`, interrupted while its monitor polled the AM, sent the
        kill on the same connection, read the poll's answer as the kill's and
        the kill's as task infos, and died with exit 1 after a clean drain
        (ROADMAP D8). A call that does not read its answer drops the connection."""
        from tony_tpu.cluster import rpc

        c, recv, cut = client_for(server), rpc._recv_frame, []

        def recv_once_cut(sock):
            if not cut and sock is c._sock:  # the client's end: the server reads through the same function
                cut.append(sock)
                raise fault("between request and response")  # the request is sent, its answer on the way
            return recv(sock)

        assert c.call("echo", poll=0) == {"poll": 0}  # the persistent connection
        monkeypatch.setattr(rpc, "_recv_frame", recv_once_cut)
        with pytest.raises(fault):
            c.call("echo", poll=1)
        assert c._sock is None and cut[0].fileno() == -1
        assert c.call("echo", kill=True) == {"kill": True}  # not {"poll": 1}
        assert c.call("echo", poll=2) == {"poll": 2}

    def test_call_with_retry_eventually_connects(self):
        srv = RpcServer(secret="")
        srv.register("ping", lambda: "pong")
        host, port = srv.address
        c = RpcClient(host, port)
        t = threading.Timer(0.3, srv.start)
        t.start()
        try:
            assert c.call_with_retry("ping", retries=30, delay_s=0.05) == "pong"
        finally:
            t.join()
            srv.stop()
