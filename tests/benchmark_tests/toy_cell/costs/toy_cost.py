def cost(shapes):
    return 2.0 * shapes["T"], 8.0 * shapes["T"]
