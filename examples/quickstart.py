"""Quickstart — the paper's Fig. 2 flow, verbatim API.

1. configure the AL service from a YAML file (config-as-a-service)
2. start the server
3. push unlabeled data from a client
4. query a budget of samples to label

Run: PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.data.synthetic import image_pool
from repro.service.client import ALClient, serve_tcp
from repro.service.config import ALServiceConfig
from repro.service.server import ALServer

EXAMPLE_YML = """
name: "IMG_CLASSIFICATION"
version: 0.1
active_learning:
  strategy:
    type: "lc"
  model:
    name: "synthetic_cnn"
    batch_size: 16
al_worker:
  protocol: "tcp"
  host: "127.0.0.1"
  port: 0
  replicas: 1
"""


def main():
    # 1. configure
    config = ALServiceConfig.from_yaml(EXAMPLE_YML)
    print(f"service: {config.name} strategy={config.strategy} "
          f"model={config.model_name}")

    # 2. start server (+ TCP endpoint, the gRPC stand-in)
    al_server = ALServer(config)
    rpc = serve_tcp(al_server, config.host, config.port)
    print(f"server listening on {config.host}:{rpc.port}")

    # 3. client pushes the unlabeled pool
    al_client = ALClient(url=f"{config.host}:{rpc.port}")
    data_list, labels = image_pool(400, seed=3)
    keys = al_client.push_data(list(data_list))
    print(f"pushed {len(keys)} samples; "
          f"cache entries: {al_client.stats()['cache']['entries']}")

    # 4. query a labeling budget
    selected = al_client.query(budget=10)
    print(f"strategy {selected['strategy']} selected "
          f"{len(selected['keys'])} samples: indices {selected['indices']}")

    # 5. human-in-the-loop: label and update the model
    key2y = dict(zip(keys, labels))
    al_client.label(selected["keys"], [key2y[k] for k in selected["keys"]])
    acc = al_client.train_eval()
    print(f"model updated on labeled set; (train-set) accuracy proxy "
          f"= {acc}")

    al_client.close()
    rpc.stop()


if __name__ == "__main__":
    main()
